"""The window arithmetic on hand-made token timestamps."""
import numpy as np
import pytest

from bench import window
from bench.window import Req


def reqs():
    return [
        # due at 0, tokens at 0.5, 0.6, 0.8: gaps 0.1 and 0.2
        Req(0, 0.0, 10, 3, [0.5, 0.6, 0.8], t_admit=0.1),
        # due at 1, first token at 1.25, then 1.75
        Req(1, 1.0, 10, 5, [1.25, 1.75], t_admit=1.05),
        # due at 2 and stalled: no token by the window's end (4 s)
        Req(2, 2.0, 10, 5, [], t_admit=None),
        # due after the window: not counted
        Req(3, 5.0, 10, 5, [], t_admit=None),
    ]


def test_ttft_counts_a_stalled_request_at_its_wait_so_far():
    assert window.ttft_samples(reqs(), 4.0) == pytest.approx(
        [0.5, 0.25, 2.0])
    assert window.pct(window.ttft_samples(reqs(), 4.0), 100) == 2.0


def test_queue_wait_counts_unadmitted_requests_at_their_wait():
    assert window.queue_wait_samples(reqs(), 4.0) == pytest.approx(
        [0.1, 0.05, 2.0])


def test_token_gaps_and_rate():
    assert sorted(window.token_gaps(reqs(), 4.0)) == pytest.approx(
        [0.1, 0.2, 0.5])
    assert window.tokens_in_window(reqs(), 4.0) == 5
    # a gap whose later token falls after the window is not counted
    assert sorted(window.token_gaps(reqs(), 1.7)) == pytest.approx(
        [0.1, 0.2])


def test_readers_on_a_run():
    from bench.harness import Run, reader
    run = Run(seconds=4.0, setup_s=12.5, cfg={},
              engine={"kv_pages": 10, "page_size": 4}, reqs=reqs(),
              decode_calls=[(0.4, 0.41, None), (0.55, 0.56, None),
                            (0.75, 0.76, None), (1.2, 1.21, None),
                            (1.7, 1.71, None)],
              compiles=[(-3.0, 1.0), (1.5, 0.2)],
              peaks=None, programs={"decode": "jit_step",
                                    "chunk": "jit_chunk_step"})
    assert reader("out_tok_s")(run) == pytest.approx(5 / 4.0)
    assert reader("itl_p95_ms")(run) == pytest.approx(
        window.pct([0.1, 0.2, 0.5], 95) * 1e3)
    assert reader("ttft_p90_s")(run) == pytest.approx(
        window.pct([0.5, 0.25, 2.0], 90))
    assert reader("batch_occupancy")(run) == pytest.approx(1.0)
    assert reader("decode_pass_ms")(run) == pytest.approx(800.0)
    assert reader("compiles_in_window")(run) == 1.0
    assert reader("setup_s")(run) == 12.5
    # device metrics read nothing without a trace or a chip
    for name in ("decode_step_ms", "prefill_chunk_ms", "attn_roofline",
                 "device_idle_share", "decode_mfu", "kv_live_share"):
        assert reader(name)(run) is None


def test_traced_readers_by_hand():
    """Live K/V share and the decode step's share of peak, from kept step
    arguments and a reduced trace with known answers."""
    from bench import shapes
    from bench.harness import Run, reader
    cfg = {"num_hidden_layers": 1, "hidden_size": 8,
           "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4,
           "intermediate_size": 16, "vocab_size": 10, "model_type": "qwen3"}
    calls = [(0.1, 0.11, np.array([3, 5])), (0.2, 0.21, np.array([4, 6])),
             (5.0, 5.01, np.array([40, 40]))]       # after the window
    peaks = {"bf16_flops": 1e6, "hbm_bytes_per_s": 1e6}
    run = Run(seconds=4.0, setup_s=1.0, cfg=cfg,
              engine={"kv_pages": 10, "page_size": 4}, reqs=reqs(),
              decode_calls=calls, compiles=[], peaks=peaks,
              programs={"decode": "jit_step", "chunk": "jit_chunk_step"},
              trace={"window_ns": 4e9, "busy_ns": 3e9,
                     "modules": {"jit_step": [2, 4_000_000]}, "ops": {}})
    # (8 + 10) / 2 live tokens of 40
    assert reader("kv_live_share")(run) == pytest.approx(100 * 9 / 40)
    flops = (shapes.decode_pass_work(cfg, [3, 5])[0]
             + shapes.decode_pass_work(cfg, [4, 6])[0]) / 2
    assert reader("decode_mfu")(run) == pytest.approx(
        100 * flops / 2e-3 / 1e6)
    assert reader("device_idle_share")(run) == pytest.approx(25.0)
    assert reader("decode_step_ms")(run) == pytest.approx(2.0)
    # no op under R.attn: the roofline share reads nothing
    assert reader("attn_roofline")(run) is None
