"""The harness's hold on the program's decode step: arguments picked by
their form, and a wrapper the engine replaced or a call count that does
not match the engine's own both raise instead of skewing the metrics."""
import types

import jax.numpy as jnp
import pytest

from bench import system


def step_args(slots=4):
    return ("params", "pages", jnp.zeros((slots, 1), jnp.int32),
            jnp.zeros((slots, 8), jnp.int32),
            jnp.arange(slots, dtype=jnp.int32),
            jnp.array([True, False, True, True][:slots]),
            jnp.zeros((2,), jnp.uint32))


def test_lengths_and_active_are_picked_by_form():
    args = step_args()
    ln, act = system.lengths_and_active(args, 4)
    assert ln is args[4] and act is args[5]
    # the same arguments in another order give the same pick
    ln2, act2 = system.lengths_and_active(args[::-1], 4)
    assert ln2 is args[4] and act2 is args[5]


@pytest.mark.parametrize("extra", [jnp.zeros((4,), jnp.int32),
                                   jnp.zeros((4,), jnp.bool_)])
def test_an_ambiguous_step_call_raises(extra):
    with pytest.raises(RuntimeError):
        system.lengths_and_active(step_args() + (extra,), 4)


def engine_with(step, steps):
    return types.SimpleNamespace(
        _pool_step=step, health=types.SimpleNamespace(taps={"steps": steps}))


def test_check_instrumented():
    calls = []
    timed = system.Timed(lambda *a: None, calls, [0.0])
    for _ in range(3):
        timed()
    system.check_instrumented(engine_with(timed, 3), calls)
    # the window closed inside the last call, before the engine counted it
    system.check_instrumented(engine_with(timed, 2), calls)
    with pytest.raises(RuntimeError, match="counted"):
        system.check_instrumented(engine_with(timed, 5), calls)
    with pytest.raises(RuntimeError, match="replaced"):
        system.check_instrumented(engine_with(lambda *a: None, 3), calls)
