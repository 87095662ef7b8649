"""The reduction of the program's own spans (``bench/progtrace.py``): on
hand-made spans with known answers, on a profile recorded here, and on a
trace of the batch-decode cell recorded on a TPU v5 lite by
``bench/record_spans.py``."""
import json
import types
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import devtrace, progtrace, window
from bench.harness import Run, reader

DATA = Path(__file__).resolve().parent / "data"
FIXTURE = DATA / "v5e-qwen3-8b-h18.batch-decode.spans.json"


def sp(name, s, e, **counters):
    return [name, s, e, counters]


def two_steps():
    """A loop of two passes: each dispatches, waits, commits, taps."""
    return [
        sp("serve.loop", 0, 1000),
        sp("serve.pass", 0, 400, chunks=1, step=1, tokens=2),
        sp("serve.admit", 0, 10, admitted=2, waiting=0),
        sp("serve.chunk_dispatch", 10, 40, rid=0, tokens=8, base=0),
        sp("serve.step_dispatch", 50, 100, active=2, live_tokens=30),
        sp("serve.step_wait", 100, 300),
        sp("serve.commit", 300, 350),
        sp("python.gc", 310, 340, generation=0),
        sp("serve.tap", 350, 400),
        sp("serve.pass", 400, 800, chunks=0, step=2, tokens=2),
        sp("serve.admit", 400, 410, admitted=0, waiting=0),
        sp("serve.step_dispatch", 420, 480, active=2, live_tokens=32),
        sp("serve.step_wait", 480, 700),
        sp("serve.commit", 700, 760),
        sp("serve.tap", 760, 800),
        sp("serve.pass", 800, 1000, chunks=0),
        sp("serve.idle", 805, 1000),
    ]


def test_extract_reads_program_spans_and_counters(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("serve.pass") as p:
                with jax.profiler.TraceAnnotation("python.gc", generation=2):
                    pass
                p.set_metadata(step=3, tokens=7)
    got = progtrace.extract(devtrace.latest_xplane(str(tmp_path)))
    assert [g[0] for g in got] == ["serve.pass", "python.gc"]
    assert got[0][3] == {"step": 3, "tokens": 7}
    assert got[1][3] == {"generation": 2}
    assert got[0][1] <= got[1][1] <= got[1][2] <= got[0][2]


def test_host_gap_pairs_each_wait_with_the_next_dispatch():
    spans = two_steps()
    # end of the first wait (300) to the end of the second dispatch (480)
    assert progtrace.host_gap_ms(spans, (0, 1000)) == pytest.approx(180e-6)
    # a dispatch outside the window is not counted
    assert progtrace.host_gap_ms(spans, (0, 450)) is None
    # a wait cut off from its dispatch at the trace's start is dropped
    cut = [s for s in spans if s[1] >= 300 or s[0] == "serve.step_wait"]
    cut = [s for s in cut if not (s[0] == "serve.step_dispatch"
                                  and s[1] < 300)]
    assert progtrace.host_gap_ms(cut, (0, 1000)) is None
    # a loop that dispatches the next step before it fetches the last
    ahead = [sp("serve.step_dispatch", 0, 10),
             sp("serve.step_dispatch", 20, 30),
             sp("serve.step_wait", 40, 60),
             sp("serve.step_dispatch", 70, 80),
             sp("serve.step_wait", 90, 100)]
    assert progtrace.host_gap_ms(ahead, (0, 1000)) == 0.0


def test_pass_and_live_token_counters():
    spans = two_steps()
    assert len(progtrace.stepped_passes(spans, (0, 1000))) == 2
    assert progtrace.live_tokens(spans, (0, 1000)) == [30, 32]
    assert progtrace.kv_live_share(
        spans, (0, 1000), {"kv_pages": 4, "page_size": 16}) == \
        pytest.approx(100.0 * 31 / 64)
    assert progtrace.kv_live_share(spans, (900, 1000),
                                   {"kv_pages": 4, "page_size": 16}) is None
    ms = progtrace.phase_ms_per_step(spans, (0, 1000))
    assert ms["serve.step_wait"] == pytest.approx((200 + 220) / 2 / 1e6)
    assert ms["serve.pass (self)"] == pytest.approx(
        (1000 - (10 + 30 + 50 + 200 + 50 + 50 + 10 + 60 + 220 + 60 + 40
                 + 195)) / 2 / 1e6)


@pytest.mark.parametrize("gap,name", [
    ((305, 345), "python.gc"),          # a collection inside the commit
    ((338, 356), "serve.commit"),       # mostly commit, then the tap
    ((120, 280), progtrace.DEVICE_LABEL),
    ((790, 1000), "serve.idle"),        # the tap's end, then idle
    ((395, 405), "serve.pass"),         # the turn of two passes
    ((1000, 1100), None),
])
def test_a_gap_is_named_after_the_phase_the_host_was_in(gap, name):
    spans = [s for s in two_steps() if s[0] != "serve.loop"]
    assert progtrace.labeller(spans)(*gap) == name


def test_idle_account():
    spans = two_steps()
    gaps = [(40, 50), (305, 345), (395, 405), (1000, 1100)]
    acct = progtrace.idle_account(gaps, spans)
    assert acct["idle_s"] == pytest.approx(160e-9)
    # all but the last gap and the passes' turn lie under a phase
    assert acct["under_phase_share"] == pytest.approx(
        100.0 * (0 + 40 + 5 + 5) / 160)
    assert acct["under_pass_share"] == pytest.approx(100.0 * 60 / 160)
    assert acct["idle_s_by_span"]["python.gc"] == pytest.approx(30e-9)
    assert acct["idle_s_by_span"]["serve.loop"] == pytest.approx(60e-9)


def test_prefill_p90_counts_an_unfinished_prefill_at_its_time_so_far():
    def req(t_admit, t_prefilled):
        return types.SimpleNamespace(t_admit=t_admit,
                                     t_prefilled=t_prefilled)
    reqs = [req(0.5, 0.6), req(1.0, 1.5), req(2.0, None),
            req(3.0, 2.5),                  # re-admitted, not prefilled
            req(3.5, 4.5),                  # prefilled after the window
            req(5.0, 5.1), req(None, None)]  # after the window; waiting
    assert progtrace.prefill_samples(reqs, 4.0) == pytest.approx(
        [0.1, 0.5, 2.0, 1.0, 0.5])
    assert progtrace.prefill_p90_s(reqs, 4.0) == pytest.approx(
        window.pct([0.1, 0.5, 2.0, 1.0, 0.5], 90))
    assert progtrace.prefill_p90_s([], 4.0) is None


@pytest.fixture(scope="module")
def recorded():
    """150 ms of the batch-decode cell's window from 5 s on, recorded on a
    TPU v5 lite: device records, host and program spans, and the
    harness's decode calls (ns from the cut's start, with the lengths of
    the slots each step served)."""
    d = json.loads(FIXTURE.read_text())
    assert d["device_kind"] == "TPU v5 lite"
    return d, (0, int(d["keep_ms"] * 1e6))


def test_recorded_passes_match_the_harness_decode_calls(recorded):
    d, win = recorded
    spans = d["spans"]
    loop, = progtrace.named(spans, "serve.loop")
    passes = progtrace.named(spans, "serve.pass")
    assert passes and all(s[1] >= loop[1] for s in passes)
    for ph in spans:
        if ph[0] not in progtrace.NESTING and win[0] <= ph[1] < win[1]:
            assert sum(p[1] <= ph[1] and ph[2] <= p[2] for p in passes) == 1
    calls = [h for h in d["record"]["host"]
             if h[0] == "bench.decode_dispatch" and win[0] <= h[1] < win[1]]
    stepped = progtrace.stepped_passes(spans, win)
    assert stepped and abs(len(stepped) - len(calls)) <= 1


def test_recorded_live_tokens_are_the_harness_kv_live_share(recorded):
    """Each harness decode call (on the harness's clock, placed on the
    trace's at the window's start) falls in a ``serve.step_dispatch``
    whose ``live_tokens`` is the sum of the lengths the harness picked out
    of the call; so ``kv_live_share`` reads the same from either."""
    d, win = recorded
    disp = progtrace.named(d["spans"], "serve.step_dispatch")
    matched = []
    for s, e, lengths in d["decode_calls"]:
        over = [min(e, x[2]) - max(s, x[1]) for x in disp]
        got = disp[int(np.argmax(over))]
        assert max(over) > 0 and got not in matched
        assert got[3]["live_tokens"] == sum(lengths)
        assert got[3]["active"] == len(lengths)
        matched.append(got)
    run = Run(seconds=d["keep_ms"] / 1e3, setup_s=0.0, cfg={},
              engine=d["engine"], reqs=[],
              decode_calls=[(s / 1e9, e / 1e9, np.asarray(ln))
                            for s, e, ln in d["decode_calls"]],
              compiles=[], peaks=None, programs=d["programs"])
    assert matched
    assert progtrace.kv_live_share(matched, (-np.inf, np.inf),
                                   d["engine"]) == pytest.approx(
        reader("kv_live_share")(run), rel=1e-12)


def test_recorded_host_gap_and_gap_labels(recorded):
    d, win = recorded
    spans = d["spans"]
    gap = progtrace.host_gap_ms(spans, win)
    passes = [s[2] - s[1] for s in progtrace.named(spans, "serve.pass")]
    assert gap is not None and 0 < gap < np.median(passes) / 1e6
    red = devtrace.reduce(d["record"], win, None, lambda a, b: (a, b),
                          n_gaps=10**6)
    label = progtrace.labeller(spans)
    names = {s[0] for s in spans} - {"serve.run", "serve.loop"}
    names = (names - {progtrace.WAIT}) | {progtrace.DEVICE_LABEL}
    assert red["gaps"]
    for (a, b), _ in red["gaps"]:
        assert label(a, b) in names
    # the device's idle time lies under the program's phases
    acct = progtrace.idle_account([g[0] for g in red["gaps"]], spans)
    assert acct["under_pass_share"] > 99.0
