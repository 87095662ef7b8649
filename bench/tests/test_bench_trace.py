"""The reduction from a profiler trace to device metrics, on a hand-made
record with known answers and on a trace recorded on a TPU v5 lite."""
import json
from pathlib import Path

import pytest

from bench import devtrace

DATA = Path(__file__).resolve().parent / "data"

HLO = """HloModule jit_step, is_scheduled=true
  %fusion.2 = f32[4,10]{1,0} fusion(%p0), kind=kOutput, calls=%fc.2, metadata={op_name="jit(step)/R.logits/dot_general" source_file="x.py" source_line=3}
  ROOT %tuple.1 = (f32[4]) tuple(%fusion.2)
"""


def record():
    step, chunk = "jit(step)/R.layer{}/R.{}/dot", "jit(chunk_step)/R.layer1"
    return {"devices": {"/device:TPU:0": {
        "modules": [["jit_step(7)", 0, 100], ["jit_chunk_step(9)", 150, 250],
                    ["jit_step(7)", 300, 400]],
        "ops": [["fusion.1", 0, 60, "jit_step", step.format(0, "attn")],
                ["fusion.2", 60, 100, "jit_step", ""],
                # no module named: the enclosing execution's is taken
                ["fusion.3", 150, 250, "", chunk + "/R.mlp/x"],
                ["fusion.1", 300, 360, "jit_step", step.format(5, "attn")],
                ["fusion.2", 360, 400, "jit_step", ""]]}},
        "host": [["bench.window", 0, 1000],
                 ["bench.decode_dispatch", 100, 140]]}


def test_scopes_from_hlo_text_and_paths():
    assert devtrace.hlo_scopes(HLO) == {
        "fusion.2": "jit(step)/R.logits/dot_general"}
    assert devtrace.scope_of("jit(step)/R.layer12/R.attn/dot") == "attn"
    assert devtrace.scope_of("jit(step)/add") == "other"
    assert devtrace.module_name("jit_step(12)") == "jit_step"


def test_op_event_names_from_a_v5e_trace():
    """A v5e trace names each op event by its whole HLO instruction."""
    got = devtrace.instruction_name(
        "%fusion.1341 = s32[256,1,1]{0,2,1:T(1,128)S(1)} fusion(s32[]"
        "{:T(128)S(6)} %copy.938), kind=kLoop, calls=%fused_computation.1687")
    assert got == "fusion.1341"
    assert devtrace.instruction_name("ROOT %tuple.1 = (f32[4]) tuple()") \
        == "tuple.1"
    assert devtrace.instruction_name("fusion.2") == "fusion.2"


def test_reduce_by_hand():
    rec = record()
    win = devtrace.window_of(rec, "bench.window", 500e-9)
    assert win == (0, 500)
    label = devtrace.host_label(rec, {"bench.decode_dispatch": "dispatch"})
    red = devtrace.reduce(rec, win, {"jit_step": devtrace.hlo_scopes(HLO)},
                          label)
    assert red["window_ns"] == 500 and red["busy_ns"] == 300
    assert red["modules"] == {"jit_step": [2, 200], "jit_chunk_step": [1, 100]}
    assert red["ops"] == {("jit_step", "attn"): 120,
                          ("jit_step", "logits"): 80,
                          ("jit_chunk_step", "mlp"): 100}
    assert red["gaps"] == [[None, 100e-9], ["dispatch", 50e-9],
                           [None, 50e-9]]


def test_window_clips_the_busy_time():
    red = devtrace.reduce(record(), (50, 350))
    # ops clipped to [50, 350): 10 + 40 + 100 + 50
    assert red["busy_ns"] == 200
    assert red["modules"] == {"jit_chunk_step": [1, 100], "jit_step": [1, 100]}



def test_reduce_a_recorded_v5e_trace():
    """150 ms of the batch-decode cell's window on a TPU v5 lite, recorded
    by ``bench/record_trace.py``: three prefill chunks, then the pool decode
    step.  Its op events carry no path of their own; the programs' HLO
    scopes attribute them."""
    d = json.loads((DATA / "v5e-qwen3-8b-h18.batch-decode.json").read_text())
    assert d["device_kind"] == "TPU v5 lite"
    rec, n = d["record"], int(d["keep_ms"] * 1e6)
    win = devtrace.window_of(rec, "bench.window", d["keep_ms"] / 1e3)
    assert win == (0, n)
    red = devtrace.reduce(rec, win, d["scopes"])
    assert 0 < red["busy_ns"] <= red["window_ns"] == n
    for role in ("decode", "chunk"):
        mod = d["programs"][role]
        n_exec, ns = red["modules"][mod]
        assert n_exec >= 1
        ops = {s: v for (m, s), v in red["ops"].items() if m == mod}
        # the ops cover most of the program's device time (the chunk
        # program idles between some of its ops), and most of that is
        # attributed to a region
        assert 0.8 * ns <= sum(ops.values()) <= 1.01 * ns
        assert ops["attn"] > 0 and ops["mlp"] > 0
        assert ops.get("other", 0) < 0.5 * ns
    bare = devtrace.reduce(rec, win)
    step = d["programs"]["decode"]
    assert {s for m, s in bare["ops"] if m == step} == {"other"}
