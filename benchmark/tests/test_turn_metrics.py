"""The three readers of a session turn's own spans (``first_token_p50_s``,
``prefill_wait_s_per_req``, ``decode_stall_s_per_req``) on synthetic profiles, and their entries.

No rehearsal is run here: the traced rehearsals of both session cells (``test_lm_cells.py``,
``test_trinity_cell.py``) already hold every listed metric to a number, and a third run of a
cell would race theirs over ``.benchmark_state/<cell>/`` (PERF.md section 7, 29)."""

from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from loading import load_module  # noqa: E402
from test_harness import bench_json  # noqa: E402

NAMES = ("first_token_p50_s", "prefill_wait_s_per_req", "decode_stall_s_per_req")
readers = {n: load_module(os.path.join(os.path.dirname(HERE), "layer_metrics", n + ".py"),
                          "bench_metric_" + n) for n in NAMES}


def span(name, duration_s, **counters):
    s = {"name": name, "start_s": 0.0, "duration_s": duration_s, "depth": 1}
    if counters:
        s["counters"] = counters
    return s


def turn(wait, admit, prefill, first, decode=None, steps=()):
    """One GENERATE frame's profile: its phases (no ``session.turn.prefill`` where ``prefill`` is
    None), ``decode`` = (chunk_steps, chunk_step_s) and the ``session.step`` spans it carries as
    (seconds, prefill_tokens)."""
    spans = [span("session.coalesce", 9.0), span("server.sched.session_wait", wait),
             span("session.admit", admit), span("session.turn.first_token", first),
             span("session.retire", 0.001), span("session.prefill", 0.002, tokens=64)]
    if prefill is not None:
        spans.append(span("session.turn.prefill", prefill, tokens=64, chunks=1, chunks_ahead=0))
    if decode is not None:
        spans.append(span("session.turn.decode", 1.0, steps=63, chunk_steps=decode[0],
                          chunk_step_s=decode[1]))
    spans += [span("session.step", s, rows=16, prefill_tokens=t) for s, t in steps]
    return {"qid": "q", "origin": "server", "spans": spans}


def run_of(profiles, requests=None):
    return {"profiles": profiles, "client_profiles": [{"qid": "q"}],
            "requests": len(profiles) if requests is None else requests}


def test_first_token_is_the_median_of_submit_to_first_id():
    profiles = [turn(0.01, 0.001, 0.2, 0.03), turn(0.02, 0.001, 0.05, 0.02),
                turn(0.03, 0.002, 0.6, 0.04)]
    assert readers["first_token_p50_s"].read(run_of(profiles)) == pytest.approx(0.241)
    # a turn without a prompt chunk has no prefill span and still has a first token
    alone = [turn(0.01, 0.001, None, 0.03)]
    assert readers["first_token_p50_s"].read(run_of(alone)) == pytest.approx(0.041)
    # a profile of another frame (an open, a close) has no first token and is left out
    other = {"qid": "q", "origin": "server", "spans": [span("server.sched.session_wait", 5.0)]}
    assert readers["first_token_p50_s"].read(run_of(alone + [other])) == pytest.approx(0.041)


def test_prefill_wait_sums_the_turns_prefill_spans_over_requests():
    profiles = [turn(0.01, 0.001, 0.2, 0.03), turn(0.02, 0.001, 0.05, 0.02),
                turn(0.01, 0.001, None, 0.03)]
    assert readers["prefill_wait_s_per_req"].read(run_of(profiles, 5)) == pytest.approx(0.25 / 5)
    # turns without a prompt chunk read 0, not nothing
    assert readers["prefill_wait_s_per_req"].read(run_of([turn(0.01, 0.001, None, 0.03)])) == 0.0


def test_the_stall_is_the_chunk_steps_seconds_over_the_median_free_step():
    # chunk-free steps of 20, 21 and 30 ms: m = 0.021
    steps = [(0.020, 0), (0.030, 0), (0.021, 0), (0.040, 512)]
    profiles = [turn(0.01, 0.001, 0.2, 0.03, decode=(10, 0.31), steps=steps),
                turn(0.01, 0.001, 0.2, 0.03, decode=(2, 0.05)),
                # steps that held only short chunks can read under m: the max(0, .) holds it at 0
                turn(0.01, 0.001, 0.2, 0.03, decode=(3, 0.05)),
                turn(0.01, 0.001, 0.2, 0.03, decode=(0, 0.0))]
    want = (0.31 - 10 * 0.021) + (0.05 - 2 * 0.021) + 0.0 + 0.0
    assert readers["decode_stall_s_per_req"].read(run_of(profiles, 8)) == pytest.approx(want / 8)


def test_without_a_chunk_free_step_m_is_zero():
    profiles = [turn(0.01, 0.001, 0.2, 0.03, decode=(2, 0.05), steps=[(0.04, 64), (0.05, 128)]),
                turn(0.01, 0.001, 0.2, 0.03, decode=(1, 0.04))]
    assert readers["decode_stall_s_per_req"].read(run_of(profiles)) == pytest.approx(0.09 / 2)


def test_nothing_to_read_is_none_and_does_not_raise():
    # the parent commit records no turn's spans, and its steps carry no prefill_tokens
    parent = {"qid": "q", "origin": "server", "spans": [
        span("session.coalesce", 2.0), span("server.sched.session_wait", 0.02),
        span("session.admit", 0.001), span("session.prefill", 0.002, tokens=64),
        span("session.step", 0.025, rows=16), span("session.retire", 0.001)]}
    for name in NAMES:
        assert readers[name].read(run_of([parent])) is None
        assert readers[name].read(run_of([])) is None
        # a profile of a query id no client of the window minted is not read
        foreign = dict(turn(0.01, 0.001, 0.2, 0.03, decode=(1, 0.04)), qid="warm-up")
        assert readers[name].read(run_of([foreign])) is None


def test_the_entries_name_both_session_cells_and_the_layer_of_decode_step_s():
    bench = bench_json()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NAMES:
        assert by_name[name] == {
            "name": name, "unit": "s", "better": "lower", "source": "program_span",
            "layer": by_name["decode_step_s"]["layer"], "moves": "request_p50_s",
            "workloads": ["olmo7b-sessions16", "trinity-sessions32"]}
