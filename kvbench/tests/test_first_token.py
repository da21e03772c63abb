"""``metrics/_first_token.py`` and the five readers of PR 59 on built
slices: the engine's ``request.first_token`` markers beside the
``step.dispatch``es, programs and requests they are joined to; and the
slice's line of ``hack/kvbench_requests.py --trace 1``, which checks the
markers against the trace around them. Times are written in ms and handed
on in ns. ``tests/test_kvbench_first_token.py`` (tier-1) collects these
cases."""

import sys
from pathlib import Path

import pytest

from kvbench.harness import names
from kvbench.harness.loop import RequestRecord, Run
from kvbench.metrics import _first_token
from kvbench.tests.test_launches import (
    MS,
    PREFILL,
    chunks_ahead,
    lone,
    pair_of_replicas,
)
from kvbench.tests.test_reduce import ev
from kvbench.trace import reduce as R

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "hack"))
from kvbench_requests import first_token_summary  # noqa: E402

FIVE = ("engine_queue_ms_p50", "behind_prefill_share", "engine_ttft_ms_p50",
        "prefill_own_device_share", "ttft_outside_engine_ms_p50")


def marker(at, request_id="r0", pod="pod-0", step=1, prompt_tokens=7000,
           cached_tokens=0, chunks=1, first_launch=1, last_launch=1,
           decodes_between=0, behind_chunks=0, queued=0.0, behind=0.0,
           prefill=0.0):
    """The marker as the engine opens it, closed at once at ``at`` ms;
    ``queued``, ``behind`` and ``prefill`` in ms."""
    return ev("request.first_token", at * MS, 0, pod=pod, step=step,
              request_id=request_id, prompt_tokens=prompt_tokens,
              cached_tokens=cached_tokens, chunks=chunks,
              first_launch=first_launch, last_launch=last_launch,
              decodes_between=decodes_between, behind_chunks=behind_chunks,
              queued_ns=int(queued * MS), behind_ns=int(behind * MS),
              prefill_ns=int(prefill * MS))


def read(run) -> dict:
    return {n: names.metric(n).compute(run) for n in FIVE}


def document(request_id="r0", **kw):
    """``chunks_ahead``'s seven chunks as one request's: 140 ms of device
    time between the step that picked it (at 0) and its first token."""
    s = chunks_ahead(**kw)
    for e in s.host:
        if e.name == "step.dispatch" and "prefill_pos" in e.stats:
            e.stats["request_id"] = request_id
    return s


def record(idx, start_s, first_token_s, prompt_len=7000):
    rec = RequestRecord(idx=idx, arrival=None, prompt_len=prompt_len,
                        max_new=8)
    rec.start, rec.sampled = start_s, True
    if first_token_s is not None:
        rec.token_times = [first_token_s]
    return rec


def test_one_document_alone():
    whole = marker(141.25, chunks=7, first_launch=1, last_launch=7,
                   decodes_between=0, queued=0.5, prefill=141.3)
    run = document().run(whole)
    run.requests = [record(0, 100.0, 100.150)]
    got = read(run)
    assert got["engine_queue_ms_p50"] == pytest.approx(0.5)
    assert got["engine_ttft_ms_p50"] == pytest.approx(141.8)
    assert got["behind_prefill_share"] == 0.0
    # Seven programs of 20 ms inside 141.3 ms of ``prefill_ns``.
    assert got["prefill_own_device_share"] == pytest.approx(
        100.0 * 140.0 / 141.3)
    # 150 ms on the harness's clock, 141.8 of them the engine's.
    assert got["ttft_outside_engine_ms_p50"] == pytest.approx(8.2)
    (m,) = _first_token.of(run)
    assert _first_token.of(run)[0] is m                # read once a run
    assert m.own_device_ns == pytest.approx(140 * MS)
    assert m.record is run.requests[0]


@pytest.mark.parametrize("numbered", [True, False], ids=["launch", "parent"])
def test_a_slice_with_no_marker_reads_zero_and_an_untraced_run_nothing(
        numbered):
    """The parent's program under these files opens no marker: 0.0, so
    that the line holds every metric its cell lists."""
    for build in (lone, pair_of_replicas, chunks_ahead):
        assert read(build(numbered=numbered).run()) == dict.fromkeys(
            FIVE, 0.0)
    assert read(Run(seconds=1.0)) == dict.fromkeys(FIVE)


def test_three_requests_one_behind_another():
    """Sums for the share, medians for the times."""
    marks = [marker(30.0, "r0", queued=2.0, behind=0.0, prefill=20.0),
             marker(40.0, "r1", "pod-1", queued=10.0, behind=8.0,
                    behind_chunks=1, prefill=20.0),
             marker(70.0, "r2", queued=30.0, behind=24.0, behind_chunks=2,
                    prefill=40.0, decodes_between=2)]
    run = pair_of_replicas().run(*marks)
    got = read(run)
    assert got["engine_queue_ms_p50"] == pytest.approx(10.0)
    assert got["engine_ttft_ms_p50"] == pytest.approx(30.0)
    assert got["behind_prefill_share"] == pytest.approx(100.0 * 32 / 122)
    # No dispatch of the slice names them: no chunk is theirs.
    assert got["prefill_own_device_share"] == 0.0
    assert [m.own_device_ns for m in _first_token.of(run)] == [None] * 3
    assert got["ttft_outside_engine_ms_p50"] == 0.0    # nobody joined
    text = first_token_summary(_first_token.of(run), run)
    assert text.startswith("3 markers, 0 joined, 0 with cached_tokens > 0")
    assert "behind_ns > queued_ns in 0;" in text
    marks[1].stats["behind_ns"] = int(11.0 * MS)
    run = pair_of_replicas().run(*marks)
    assert "behind_ns > queued_ns in 1;" in first_token_summary(
        _first_token.of(run), run)


def test_own_device_share_under_a_shifted_clock():
    """A host clock that lies 6 ms off the device's: programs lie outside
    their phases (the first chunk and the decode step here; the chunks sent
    ahead still fit), faults are no exception, so every placed program is
    read (``_launches.timed``), and durations do not move."""
    whole = marker(141.25 + 6.0, chunks=7, first_launch=1, last_launch=7,
                   queued=0.5, prefill=141.3)
    run = document(host_shift=6.0).run(whole)
    assert names.metric("prefill_own_device_share").compute(
        run) == pytest.approx(100.0 * 140.0 / 141.3)
    found = run.launches
    assert found.clock_faults == 2 and found.timed() == found.placed
    assert found.placed[0].off > 0            # the document's first chunk
    # Where a fault is the exception its program is kept out, and a
    # request one of whose chunks has no trusted time is left out whole.
    s = document()
    for i in range(40):
        t = 150.0 + 9.0 * i
        s.launch("pod-0", "forward_decode_pallas", (t, t + 0.5),
                 (t + 0.6, t + 6.6), fetch=(t + 0.5, t + 6.9), step=3 + i)
    first = next(e for e in s.host if e.name == "step.dispatch")
    first.start += 2.0 * MS                   # opens after its program began
    run = s.run(marker(141.25, chunks=7, first_launch=1, last_launch=7,
                       queued=0.5, prefill=141.3))
    assert names.metric("prefill_own_device_share").compute(run) == 0.0
    assert run.launches.clock_faults == 1
    assert run.launches.timed() == run.launches.pairs


def test_a_marker_whose_chunks_the_slice_cut_is_left_out():
    """Its first chunk was launched before the slice began: neither its
    device time nor its ``prefill_ns`` is in the share."""
    cut = marker(141.25, chunks=9, first_launch=-1, last_launch=7,
                 queued=0.5, prefill=181.3)
    run = document().run(cut)
    assert names.metric("prefill_own_device_share").compute(run) == 0.0
    (m,) = _first_token.of(run)
    assert m.own_device_ns is None
    assert "chunks differ from the slice's dispatches in 0 of 0" in (
        first_token_summary([m], run))
    # Beside a whole one it changes nothing.
    s = document()
    s.launch("pod-0", PREFILL, (150.0, 150.5), (150.6, 160.6),
             fetch=(150.5, 160.9), step=3)
    s.host[-2].stats["request_id"] = "r1"
    whole = marker(161.0, "r1", chunks=1, first_launch=9, last_launch=9,
                   prefill=11.0, step=3)
    run = s.run(cut, whole)
    assert names.metric("prefill_own_device_share").compute(
        run) == pytest.approx(100.0 * 10.0 / 11.0)


def test_a_dispatch_outside_the_markers_launches_is_not_its_chunk():
    """A request id names one request of a window; a dispatch that bears
    it with a ``launch`` outside the marker's belongs to another life."""
    whole = marker(141.25, chunks=5, first_launch=3, last_launch=7,
                   prefill=101.3)
    run = document().run(whole)
    (m,) = _first_token.of(run)
    assert m.own_device_ns == pytest.approx(100 * MS)
    assert "chunks differ from the slice's dispatches in 0 of 1" in (
        first_token_summary([m], run))


def test_outside_the_engine_joins_the_windows_own_requests_only():
    marks = [marker(30.0, "r0", queued=2.0, prefill=20.0),
             marker(40.0, "r1", queued=10.0, prefill=20.0,
                    prompt_tokens=512),        # another prompt: not r1
             marker(50.0, "setup3", queued=1.0, prefill=9.0),
             marker(60.0, "r2", queued=3.0, prefill=30.0),
             marker(70.0, "r3", queued=3.0, prefill=30.0)]
    run = lone().run(*marks)
    run.requests = [record(0, 10.0, 10.030), record(1, 11.0, 11.050),
                    record(2, 12.0, 12.043), record(3, 13.0, None)]
    got = names.metric("ttft_outside_engine_ms_p50").compute(run)
    # r0: 30 - 22; r2: 43 - 33; r3 has no first token on the harness's side.
    assert got == pytest.approx(9.0)
    assert [m.record is not None for m in _first_token.of(run)] == [
        True, False, False, True, True]
    assert first_token_summary(_first_token.of(run), run).startswith(
        "5 markers, 2 joined")


def test_the_summary_checks_the_marker_against_the_trace_around_it():
    """``queued_ns + prefill_ns`` beside the trace's own interval from the
    request's ``enqueue.admit`` to the marker, and ``chunks`` beside the
    slice's dispatches."""
    admit = ev("enqueue.admit", -1.0 * MS, 0.4 * MS, pod="pod-0", step=0,
               request_id="r0")
    whole = marker(141.25, chunks=7, first_launch=1, last_launch=7,
                   queued=0.5, prefill=141.3)
    run = document().run(admit, whole)
    text = first_token_summary(_first_token.of(run), run)
    # 141.8 told, 141.25 - (-0.6) = 141.85 on the trace's clock.
    assert "by at most 0.05 ms over 1 whose admission" in text
    assert "chunks differ from the slice's dispatches in 0 of 1" in text
    wrong = marker(141.25, chunks=6, first_launch=1, last_launch=7,
                   queued=0.5, prefill=141.3)
    run = document().run(admit, wrong)
    assert "in 1 of 1 whose first chunk it holds" in first_token_summary(
        _first_token.of(run), run)


def test_every_attribute_the_engine_gives_is_read():
    """The marker's attributes, but ``pod`` and ``step`` that every phase
    carries, are the fields the readers and ``hack/kvbench_requests.py``'s
    lines are made from."""
    from llmd_kv_cache_tpu.telemetry.engine_telemetry import _ReqState

    st = _ReqState("r0", 1.0, 0, None)
    st.first_token_ts, st.sched_ts = 3.0, 2.0
    fields = set(_first_token.Marker.__dataclass_fields__) - {
        "event", "record", "own_device_ns"}
    assert set(st.first_token_split()) == fields
    m = _first_token._marker(marker(1.0, **{
        k: 5 for k in fields - {"request_id", "queued_ns", "behind_ns",
                                "prefill_ns"}}))
    assert (m.chunks, m.behind_chunks, m.request_id) == (5, 5, "r0")


def test_the_cells_that_report_them():
    """Each of the five stands once in ``BENCHMARK.json``, wherever, as its
    module says, and all five name the same cells."""
    bench = names.benchmark()
    entries = {}
    for name in FIVE:
        (entries[name],) = [m for m in bench["per_layer"]
                            if m["name"] == name]
        entry, mod = entries[name], names.metric(name)
        assert (entry["source"], entry["layer"], entry["moves"]) == (
            mod.SOURCE, mod.LAYER, mod.MOVES) == (
            "program_span", "scheduler", "ttft_p50_ms")
        assert entry["unit"] == mod.UNIT
        assert entry["workloads"] == entries[FIVE[0]]["workloads"]


def test_load_keeps_an_event_as_short_as_the_marker(tmp_path):
    """``trace/reduce.py: load`` keeps a kept name's event whatever its
    length (the ``step.work`` marker is the precedent), its attributes
    with it, and ``reduce`` hands it on whole."""
    import jax
    from jax.profiler import TraceAnnotation

    from kvbench.run import spans_of
    from llmd_kv_cache_tpu.telemetry.tracing import PHASE_NAMES

    assert _first_token.MARKER in PHASE_NAMES
    spans = spans_of(PHASE_NAMES)
    assert spans.index(_first_token.MARKER) < spans.index("step.fetch")
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("step", pod="pod-0"):
            with TraceAnnotation(_first_token.MARKER, pod="pod-0", step=3,
                                 request_id="r7", prompt_tokens=7000,
                                 cached_tokens=6400, chunks=2,
                                 first_launch=11, last_launch=13,
                                 decodes_between=1, behind_chunks=4,
                                 queued_ns=2_000_000_000_000,
                                 behind_ns=1_500_000, prefill_ns=80_000_000):
                pass
    finally:
        jax.profiler.stop_trace()
    planes = R.load(R.find_xplane(str(tmp_path)), spans)
    reduced = R.reduce(planes, 1, spans)
    (event,) = reduced.events[_first_token.MARKER]
    assert 0 <= event.dur < 1 * MS
    m = _first_token._marker(event)
    assert (m.request_id, m.cached_tokens, m.first_launch, m.last_launch,
            m.queued_ns, m.behind_ns) == (
        "r7", 6400, 11, 13, 2_000_000_000_000, 1_500_000)
    # It stands inside the step it was opened in, on the trace's clock.
    (step,) = reduced.events["step"]
    assert step.start <= event.start <= event.end <= step.end
