"""End-to-end generation tracing: request-scoped spans from client to
decode slot, TTFT/ITL latency attribution, and the crash flight
recorder (observability/tracing.py + serving/continuous.py +
serving/flight.py + serving/router.py + parallel/serving.py).

The load-bearing pins:
  * trace-id PROPAGATION: one traceparent-style id rides the wire meta
    next to request_id — client -> router -> server -> admission ->
    decode slot — and comes back in the response; every span a leg
    records carries it in args, which is what the merge keys on;
  * one TIMELINE per logical request: a generation that migrated
    across replicas (or recovered from the journal after a cold
    restart) leaves one trace doc per process;
    `merge_chrome_traces` rebases their clocks, namespaces their
    pids/flow-ids, and binds consecutive legs with "trace-leg" flow
    arrows into ONE Perfetto-loadable document;
  * LATENCY ATTRIBUTION: TTFT / inter-token / queue-wait histograms
    (labeled by tenant class) observed on every generation — tracer or
    not — from pre-measured intervals drained OUTSIDE the step lock;
    /status carries the engine-local p50/p99, the dashboard grows a
    "decode latency" line, and slo_sample/SLOPolicy gate rollouts on
    ttft_p99;
  * the crash FLIGHT RECORDER: a bounded ring of step events dumped
    atomically on quarantine/restart (and SIGUSR2), reaped by the
    conftest fixture like stray journals.
"""

import json
import os
import random
import signal
import threading
import time

import pytest

from deeplearning4j_tpu.engine.decode_program import DecodeProgram
from deeplearning4j_tpu.observability.metrics import (
    REGISTERED_METRICS,
    get_registry,
)
from deeplearning4j_tpu.observability.tracing import (
    Tracer,
    merge_chrome_traces,
    new_trace_id,
)
from deeplearning4j_tpu.resilience.faults import injector
from deeplearning4j_tpu.resilience.retry import Retry
from deeplearning4j_tpu.serving.continuous import (
    DecodeEngine,
    sequential_decode,
)
from deeplearning4j_tpu.serving.flight import (
    FlightRecorder,
    install_signal_dump,
    load_dump,
    reap_stray_flight_dumps,
)
from deeplearning4j_tpu.zoo.decoder import CausalTransformer

pytestmark = pytest.mark.trace

VOCAB, CTX, SLOTS, PAGE = 64, 64, 4, 8


@pytest.fixture(scope="module")
def program():
    model = CausalTransformer(vocab_size=VOCAB, d_model=32, n_heads=4,
                              n_layers=2, max_ctx=CTX, seed=3).init()
    prog = DecodeProgram(model, max_slots=SLOTS, page_size=PAGE)
    prog.warmup(prog.init_kv())
    return prog


def _drive(eng, handles, max_steps=2000):
    steps = 0
    while any(not h.done for h in handles):
        eng.step_once()
        steps += 1
        assert steps < max_steps, "engine made no progress"


def _spans(doc, name=None, trace=None):
    out = []
    for ev in doc["traceEvents"]:
        if ev.get("ph") != "X":
            continue
        if name is not None and ev.get("name") != name:
            continue
        if trace is not None \
                and (ev.get("args") or {}).get("trace") != trace:
            continue
        out.append(ev)
    return out


# ======================================================== registry pins
def test_trace_registry_names():
    """The latency-attribution histograms and the flight-dump counter
    are registered under their canonical literal names (the
    conformance pass cross-checks these against emission sites)."""
    assert {"dl4j_decode_ttft_seconds",
            "dl4j_decode_itl_seconds",
            "dl4j_decode_queue_wait_seconds",
            "dl4j_decode_flight_dumps_total"} \
        <= set(REGISTERED_METRICS)


# ================================================== engine-level tracing
def test_engine_spans_and_trace_id_minting(program):
    """An engine with a tracer mints a trace id per generation and
    records the whole span tree: root `generate` span, admission wait,
    prefill chunks, and one `token` record per decoded token — all
    carrying the trace id in args."""
    tracer = Tracer()
    eng = DecodeEngine(program=program, tracer=tracer)
    h = eng.submit([5, 9, 11, 2], max_new_tokens=6, tenant="gold")
    _drive(eng, [h])
    assert h.trace and len(h.trace) == 16
    doc = tracer.export_chrome_trace()
    gen = _spans(doc, name="generate", trace=h.trace)
    assert len(gen) == 1
    assert gen[0]["args"]["tenant"] == "gold"
    assert gen[0]["args"]["finish_reason"] == "length"
    toks = _spans(doc, name="token", trace=h.trace)
    assert len(toks) == 6
    assert toks[0]["args"].get("first") is True
    assert _spans(doc, name="admission_wait", trace=h.trace)
    # the span times the chunk program's dispatch, and says so
    assert _spans(doc, name="prefill_chunk_dispatch", trace=h.trace)
    assert not _spans(doc, name="prefill_chunk", trace=h.trace)
    # a caller-supplied id wins over minting
    h2 = eng.submit([1, 2, 3], max_new_tokens=2,
                    trace="cafe0000cafe0000")
    _drive(eng, [h2])
    assert h2.trace == "cafe0000cafe0000"
    assert _spans(tracer.export_chrome_trace(), name="token",
                  trace="cafe0000cafe0000")


def test_latency_histograms_observed_without_tracer(program):
    """TTFT/ITL/queue-wait attribution is NOT gated on the tracer:
    a plain engine still observes the tenant-labeled histograms, and
    stats() surfaces the engine-local p50/p99 rings plus the program's
    dispatch tally."""
    reg = get_registry()

    def counts():
        hists = reg.snapshot()["histograms"]
        return tuple(
            hists.get(f'{name}{{tenant="gold"}}', {}).get("count", 0)
            for name in ("dl4j_decode_ttft_seconds",
                         "dl4j_decode_itl_seconds",
                         "dl4j_decode_queue_wait_seconds"))

    before = counts()
    eng = DecodeEngine(program=program)
    assert eng.tracer is None
    h = eng.submit([3, 1, 4, 1, 5], max_new_tokens=5, tenant="gold")
    _drive(eng, [h])
    after = counts()
    assert after[0] == before[0] + 1          # one first token
    assert after[1] == before[1] + 4          # 4 inter-token gaps
    assert after[2] == before[2] + 1          # one placement
    lat = eng.stats()["latency"]
    for key in ("ttft_p50_s", "ttft_p99_s", "itl_p50_s", "itl_p99_s",
                "queue_wait_p50_s", "queue_wait_p99_s"):
        assert lat[key] is not None and lat[key] >= 0.0
    disp = eng.stats()["dispatches"]
    assert disp["step"] > 0 and disp["chunk"] > 0


# ========================================================= HTTP surface
def test_trace_propagates_over_http_and_status(program):
    """The wire carries the trace id next to request_id (npz meta and
    JSON body alike): the response echoes it, the server's span tree
    records it, and /status decode facts surface the latency quantiles
    + flight-recorder state."""
    from deeplearning4j_tpu.parallel.serving import (
        ModelClient,
        ModelServer,
    )

    tracer = Tracer()
    eng = DecodeEngine(program=program)
    server = ModelServer(port=0, decode_engine=eng,
                         model_name="decoder", tracer=tracer).start()
    try:
        # the engine inherits the server's tracer
        assert eng.tracer is tracer
        client = ModelClient(f"http://127.0.0.1:{server.port}",
                             breaker=None)
        tid = new_trace_id()
        resp = client.generate([5, 9, 11], max_new_tokens=4,
                               model="decoder", trace=tid)
        assert resp["trace"] == tid
        # JSON wire: no caller id -> the server mints one and echoes it
        jclient = ModelClient(f"http://127.0.0.1:{server.port}",
                              wire="json", breaker=None)
        jresp = jclient.generate([5, 9, 11], max_new_tokens=4,
                                 model="decoder")
        assert jresp["trace"] and jresp["trace"] != tid
        doc = tracer.export_chrome_trace()
        assert _spans(doc, name="rpc.generate", trace=tid)
        assert _spans(doc, name="generate", trace=tid)
        assert len(_spans(doc, name="token", trace=tid)) == 4
        dec = client.status()["decode"]["decoder"]
        assert dec["latency"]["ttft_p99_s"] is not None
        assert dec["flight"]["capacity"] > 0
        assert dec["flight"]["dumps"] == 0
        assert dec["tracing"]["recorded"] > 0
    finally:
        server.stop()


# ============================================ cross-replica merged story
def test_migrated_generation_merges_into_one_timeline(program):
    """The acceptance drill: a generation starts on replica A, A
    retires mid-flight, the router migrates the resumable partial to
    replica B — three trace docs (client + two replicas), ONE trace
    id, merged into one timeline whose legs are bound by "trace-leg"
    flow arrows, with per-token spans on both replicas."""
    from deeplearning4j_tpu.parallel.serving import (
        ModelClient,
        ModelServer,
    )
    from deeplearning4j_tpu.serving import ReplicaRouter

    tr_client, tr_a, tr_b = Tracer(), Tracer(), Tracer()
    ea = DecodeEngine(program=program)
    eb = DecodeEngine(program=program)
    sa = ModelServer(port=0, decode_engine=ea, model_name="decoder",
                     tracer=tr_a).start()
    sb = ModelServer(port=0, decode_engine=eb, model_name="decoder",
                     tracer=tr_b).start()
    try:
        router = ReplicaRouter(
            [f"http://127.0.0.1:{sa.port}",
             f"http://127.0.0.1:{sb.port}"],
            client_factory=lambda u: ModelClient(
                u, breaker=None, retry=Retry(max_attempts=1)),
            tracer=tr_client)
        prompt = [8, 1, 13, 4]
        _, oracle = sequential_decode(program, prompt, 40)
        box = {}

        def call():
            box["resp"] = router.generate(prompt, max_new_tokens=40,
                                          model="decoder",
                                          timeout_s=30.0)

        t = threading.Thread(target=call, name="trace-migrate")
        t.start()
        deadline = time.monotonic() + 10.0
        while ea.stats()["tokens_total"] < 3:
            assert time.monotonic() < deadline, "A never took the call"
            time.sleep(0.002)
        sa.stop()     # graceful retire: resumable 503 + migration
        t.join(timeout=30.0)
        assert not t.is_alive()
        resp = box["resp"]
        assert resp["tokens"] == oracle   # tracing never costs bytes
        assert resp["migrations"] == 1
        tid = resp["trace"]
        assert tid
        # ---- each process exported its own doc; the merge is ONE story
        merged = merge_chrome_traces(
            [tr_client.export_chrome_trace(),
             tr_a.export_chrome_trace(),
             tr_b.export_chrome_trace()],
            labels=["client", "replica-a", "replica-b"])
        assert merged["otherData"]["merged_docs"] == 3
        spans = _spans(merged, trace=tid)
        pids = {ev["pid"] for ev in spans}
        assert len(pids) == 3             # client + both replicas
        # both replica legs decoded tokens under the one trace id
        tok_pids = {ev["pid"] for ev in spans if ev["name"] == "token"}
        assert len(tok_pids) == 2
        # the client doc shows one leg per replica attempt
        legs = [ev for ev in spans if ev["name"] == "client.leg"]
        assert sorted(ev["args"]["ok"] for ev in legs) == [False, True]
        # consecutive legs are bound by trace-leg flow arrows
        starts = [ev for ev in merged["traceEvents"]
                  if ev.get("ph") == "s" and ev["name"] == "trace-leg"
                  and ev["id"].startswith(f"trace.{tid}.")]
        finishes = [ev for ev in merged["traceEvents"]
                    if ev.get("ph") == "f" and ev["name"] == "trace-leg"
                    and ev["id"].startswith(f"trace.{tid}.")]
        assert len(starts) == 2 and len(finishes) == 2   # 3 legs
        assert all(ev.get("bp") == "e" for ev in finishes)
        assert {ev["id"] for ev in starts} \
            == {ev["id"] for ev in finishes}
        # the merged doc is a plain JSON document (Perfetto-loadable)
        json.dumps(merged)
    finally:
        sa.stop()
        sb.stop()


def test_journal_recovery_leg_carries_trace_id(program, tmp_path):
    """Cold-restart continuity: the trace id is journaled with the
    admitted record, so the recovery leg on a fresh engine rejoins the
    original timeline under the SAME id (and the recovered stream
    stays bitwise equal to the oracle)."""
    from deeplearning4j_tpu.serving.journal import GenerationJournal

    jdir = str(tmp_path / "journal")
    prompt, mx = [5, 11, 2, 7], 20
    _, want = sequential_decode(program, prompt, mx)
    j1 = GenerationJournal(jdir, fsync_interval_s=0.0)
    eng1 = DecodeEngine(program=program, tracer=Tracer(), journal=j1)
    h1 = eng1.submit(prompt, mx, request_id="trace-drill-0")
    tid = h1.trace
    assert tid
    for _ in range(6):          # a few tokens, then the crash
        eng1.step_once()
    assert not h1.done
    j1.close()                  # hard stop: the request is still live
    # ---- cold restart on the same directory
    j2 = GenerationJournal(jdir, fsync_interval_s=0.0)
    assert "trace-drill-0" in j2.live()
    assert j2.live()["trace-drill-0"]["trace"] == tid
    tr2 = Tracer()
    eng2 = DecodeEngine(program=program, tracer=tr2)
    eng2.attach_journal(j2, recover=True)
    # the idempotent re-submit joins the recovered stream
    h2 = eng2.submit(prompt, mx, request_id="trace-drill-0")
    assert h2.trace == tid
    _drive(eng2, [h2])
    assert h2.result(timeout_s=0) == want
    assert _spans(tr2.export_chrome_trace(), name="token", trace=tid)
    j2.close()


# ====================================================== flight recorder
def test_flight_recorder_ring_dump_and_reap(tmp_path):
    """The ring is bounded, the dump is an atomic JSON document, and
    the module-level reaper removes every dump it wrote."""
    rec = FlightRecorder(capacity=16, dump_dir=str(tmp_path),
                         name="ringtest")
    for i in range(40):
        rec.note("join", i, slot=i % 4)
    assert rec.stats()["events"] == 16            # bounded
    assert rec.events()[0]["step"] == 24          # oldest dropped
    path = rec.dump("unit")
    assert path is not None and os.path.exists(path)
    doc = load_dump(path)
    assert doc["name"] == "ringtest"
    assert doc["reason"] == "unit"
    assert len(doc["events"]) == 16
    assert doc["events"][-1] == {
        "t_s": doc["events"][-1]["t_s"], "step": 39, "kind": "join",
        "slot": 3}
    assert rec.stats() == {"events": 16, "capacity": 16, "dumps": 1,
                           "last_dump": path, "last_reason": "unit"}
    # no half-written dump can masquerade as a whole one
    assert not [p for p in os.listdir(str(tmp_path))
                if p.endswith(".tmp")]
    reap_stray_flight_dumps()
    assert not os.path.exists(path)


def test_quarantine_dumps_flight_recorder(program, tmp_path):
    """A slot quarantine (decode.nonfinite) flags a dump reason under
    the step lock; step_once writes the postmortem AFTER releasing it,
    and the dump tells the quarantine story (join/chunk/quarantine
    events) with the metric counted."""
    reg = get_registry()
    d0 = reg.counter_value("dl4j_decode_flight_dumps_total",
                           labels={"reason": "quarantine"})
    injector().inject("decode.nonfinite", mode="raise", at_hit=3,
                      times=1)
    eng = DecodeEngine(program=program, flight_dir=str(tmp_path))
    rng = random.Random(11)
    reqs = [([rng.randrange(VOCAB) for _ in range(4)], 6)
            for _ in range(4)]
    oracle = []
    for p, mx in reqs:
        _, toks = sequential_decode(program, p, mx)
        oracle.append(toks)
    handles = [eng.submit(p, mx) for p, mx in reqs]
    _drive(eng, handles)
    assert [h.result(timeout_s=0) for h in handles] == oracle
    flight = eng.stats()["flight"]
    assert flight["dumps"] == 1
    assert flight["last_reason"] == "quarantine"
    doc = load_dump(flight["last_dump"])
    kinds = {ev["kind"] for ev in doc["events"]}
    assert "quarantine" in kinds and "join" in kinds
    assert reg.counter_value("dl4j_decode_flight_dumps_total",
                             labels={"reason": "quarantine"}) == d0 + 1


def test_sigusr2_dumps_live_recorders(tmp_path):
    """install_signal_dump: kill -USR2 is the live-postmortem path —
    every live recorder dumps with reason "sigusr2"; the previous
    handler is chained (and the conftest restores the original)."""
    if not hasattr(signal, "SIGUSR2"):
        pytest.skip("no SIGUSR2 on this platform")
    rec = FlightRecorder(capacity=8, dump_dir=str(tmp_path),
                         name="sigtest")
    rec.note("join", 1, slot=0)
    chained = []
    signal.signal(signal.SIGUSR2, lambda s, f: chained.append(s))
    install_signal_dump()
    os.kill(os.getpid(), signal.SIGUSR2)
    deadline = time.monotonic() + 5.0
    while rec.stats()["dumps"] < 1:
        assert time.monotonic() < deadline, "signal dump never landed"
        time.sleep(0.01)
    assert rec.stats()["last_reason"] == "sigusr2"
    assert chained == [signal.SIGUSR2]       # previous handler chained
    assert load_dump(rec.stats()["last_dump"])["events"]


# ==================================================== dashboard and SLO
def test_dashboard_decode_latency_line():
    from deeplearning4j_tpu.stats.dashboard import telemetry_lines

    snapshot = {
        "counters": {},
        "gauges": {},
        "histograms": {
            'dl4j_decode_ttft_seconds{tenant="gold"}': {
                "count": 4, "sum": 0.08, "p50": 0.010, "p99": 0.050},
            'dl4j_decode_ttft_seconds{tenant="bronze"}': {
                "count": 2, "sum": 0.30, "p50": 0.020, "p99": 0.200},
            'dl4j_decode_itl_seconds{tenant="gold"}': {
                "count": 40, "sum": 0.08, "p50": 0.002, "p99": 0.004},
            'dl4j_decode_queue_wait_seconds{tenant="gold"}': {
                "count": 4, "sum": 0.006, "p50": 0.001, "p99": 0.0015},
        },
    }
    lines = telemetry_lines(snapshot)
    lat = [l for l in lines if l.startswith("decode latency — ")]
    # worst label set per quantile: bronze's ttft dominates gold's
    assert lat == [
        "decode latency — ttft p50 20.0ms p99 200.0ms · "
        "itl p50 2.0ms p99 4.0ms · queue wait p99 1.5ms"]
    # quiet domain -> no line
    assert not [l for l in telemetry_lines(
        {"counters": {}, "gauges": {}, "histograms": {}})
        if l.startswith("decode latency")]


def test_slo_gates_on_ttft_p99():
    """slo_sample derives ttft_p99_s from the histogram bucket deltas;
    SLOPolicy's `ttft_p99<...` clause parses, round-trips through
    to_spec, and breaches on a slow sample."""
    from deeplearning4j_tpu.serving.controller import (
        SLOPolicy,
        slo_sample,
    )

    prev = {"counters": {}, "gauges": {}, "histograms": {}}
    cur = {
        "counters": {"dl4j_serving_requests_total": {"": 100.0}},
        "gauges": {},
        "histograms": {
            'dl4j_decode_ttft_seconds{tenant="gold"}': {
                "count": 100,
                "buckets": {"0.05": 99, "+Inf": 1}},
        },
    }
    sample = slo_sample(prev, cur)
    assert sample["ttft_p99_s"] == pytest.approx(0.05)
    pol = SLOPolicy.parse("ttft_p99<40ms,min_requests=10")
    assert pol.max_ttft_p99_s == pytest.approx(0.04)
    assert "ttft_p99<40ms" in pol.to_spec()
    reason = pol.breach(sample, None)
    assert reason is not None and "ttft_p99" in reason
    assert SLOPolicy.parse("ttft_p99<60ms").breach(sample, None) is None
    # no ttft traffic in the window -> the clause stays quiet
    quiet = dict(sample, ttft_p99_s=None)
    assert pol.breach(quiet, None) is None


# ================================================== merge doc mechanics
def test_merge_rebases_clocks_and_namespaces_flows():
    """merge_chrome_traces aligns docs by wall-clock origin (shift in
    microseconds), gives each doc its own pid + process_name metadata,
    and namespaces per-doc flow ids so same-name flows can't collide."""
    t1, t2 = Tracer(), Tracer()
    tid = new_trace_id()
    a = time.perf_counter()
    t1.record("generate", a, a + 0.01, cat="decode",
              args={"trace": tid})
    b = time.perf_counter()
    t2.record("generate", b, b + 0.01, cat="decode",
              args={"trace": tid})
    d1, d2 = t1.export_chrome_trace(), t2.export_chrome_trace()
    # force a visible clock skew between the docs
    d2["otherData"]["unix_time_origin_s"] = \
        float(d1["otherData"]["unix_time_origin_s"]) + 2.0
    merged = merge_chrome_traces([d1, d2], labels=["p0", "p1"])
    names = {(ev["pid"], ev["args"]["name"])
             for ev in merged["traceEvents"]
             if ev.get("ph") == "M" and ev["name"] == "process_name"}
    assert names == {(1, "p0"), (2, "p1")}
    s1 = _spans(merged, name="generate", trace=tid)
    assert {ev["pid"] for ev in s1} == {1, 2}
    ts = {ev["pid"]: ev["ts"] for ev in s1}
    assert ts[2] - ts[1] >= 1.9e6       # the 2s skew survived, in us
    # base origin is the minimum of the inputs
    assert merged["otherData"]["unix_time_origin_s"] \
        == pytest.approx(float(d1["otherData"]["unix_time_origin_s"]))
