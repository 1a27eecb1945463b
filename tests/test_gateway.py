"""Multi-client gateway: session mux over one accept loop, per-session
ledgers, shared garbling cache, admission control, and teardown.

The acceptance bar (ISSUE 7): >= 4 concurrent TCP client sessions behind
ONE listener, outputs bit-identical to the single-client in-process
``PiTSession.run``, exactly one garbled slab per distinct netlist across
all sessions, bounded pools shedding with retry-after hints, and a
mid-session kill that returns its bundles without touching anyone else.
"""

import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.config import PrivacyConfig
from repro.core.engine import PrivateTransformer, random_weights
from repro.net import InProcPipe, TcpListener
from repro.serve import BundlePoolEmpty, NetPrivateServeEngine, PitGateway, \
    gateway_client

D, HEADS, DFF, S = 8, 2, 16, 4


def _model(seed=0):
    rng = np.random.default_rng(seed)
    weights = random_weights(rng, D, DFF, 1)
    pcfg = PrivacyConfig(he_poly_n=256, he_num_primes=3, he_t_bits=40,
                         frac_bits=6)
    return PrivateTransformer(pcfg, D, HEADS, DFF, weights, seed=seed)


def _inproc_engine(gw, *, seed, pool_target=2, timeout=120):
    """One pipelined client (offline + online pair) over InProc pipes."""
    off_c, off_s = InProcPipe.make_pair()
    on_c, on_s = InProcPipe.make_pair()
    gw.serve_transport(off_s, timeout=timeout)
    gw.serve_transport(on_s, timeout=timeout)
    return NetPrivateServeEngine(off_c, on_c, pool_target=pool_target,
                                 seed=seed, impl="ref", timeout=timeout)


def _wait(pred, timeout=20.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {what}")


# ---------------------------------------------------------------------------
# accept loop
# ---------------------------------------------------------------------------


def test_accept_loop_serves_many_and_stops():
    from repro.net import TcpTransport

    lst = TcpListener()
    seen = []
    loop = lst.accept_loop(seen.append, accept_timeout=0.1)
    clis = [TcpTransport.connect("127.0.0.1", lst.port) for _ in range(3)]
    assert loop.wait_accepted(3, timeout=10)
    assert loop.accepted == 3 and loop.error is None
    loop.stop()
    loop.join(timeout=5)
    assert not loop.alive
    for c in clis + seen:
        c.close()
    lst.close()


def test_accept_loop_max_accepts():
    from repro.net import TcpTransport

    lst = TcpListener()
    seen = []
    loop = lst.accept_loop(seen.append, accept_timeout=0.1, max_accepts=1)
    c1 = TcpTransport.connect("127.0.0.1", lst.port)
    assert loop.wait_accepted(1, timeout=10)
    loop.join(timeout=5)  # exits on its own once the bound is reached
    assert not loop.alive and loop.accepted == 1
    for c in seen + [c1]:
        c.close()
    lst.close()


# ---------------------------------------------------------------------------
# the acceptance-criteria test: 4 concurrent TCP sessions, one listener
# ---------------------------------------------------------------------------


def test_gateway_four_tcp_sessions_bit_identical():
    tracer = obs.enable()
    try:
        _four_tcp_sessions()
    finally:
        obs.disable()
    # the evaluator's table codec sits under the gateway's prep span
    paths = {sp.path for sp in tracer.finished_spans()}
    assert "offline/gateway.prep/tables.unpack" in paths, sorted(paths)
    assert "offline/gateway.prep/he.offline/share.matmul" in paths


def _four_tcp_sessions():
    model = _model(seed=11)
    gw = PitGateway(model, S, impl="ref", max_sessions=8, pool_cap=4)
    lst = TcpListener()
    loop = gw.serve_listener(lst, accept_timeout=0.2, timeout=120)

    rng = np.random.default_rng(12)
    xs = [rng.normal(0, 1, (S, D)) for _ in range(4)]
    engines = [None] * 4
    outs = [None] * 4
    errs = []

    def client(i):
        try:
            eng = gateway_client("127.0.0.1", lst.port, seed=100 + i,
                                 impl="ref", timeout=120)
            engines[i] = eng
            eng.preprocess(1)
            outs[i] = eng.run(xs[i])
        except Exception as e:  # surfaced below — threads swallow raises
            errs.append((i, e))

    threads = [threading.Thread(target=client, args=(i,)) for i in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=240)
    assert not errs, errs
    assert loop.accepted == 8  # 4 clients x (offline + online)

    # bit-identical to the single-client in-process session
    sess = model.compile_session(S, impl="ref")
    for i, x in enumerate(xs):
        assert np.array_equal(outs[i], sess.run(x, sess.preprocess(1)[0])), i

    st = gw.stats()
    assert st["sessions_active"] == 4 and st["sessions_admitted"] == 4
    sids = [s["sid"] for s in st["sessions"]]
    assert len(set(sids)) == 4  # one SessionState per client

    # per-session ledgers: each session metered its own full transcript,
    # and the client-side ledger agrees tag-for-tag with the server side
    by_token = {s["client"]: s for s in st["sessions"]}
    for eng in engines:
        srv_side = by_token[eng._shared.client_token]
        assert srv_side["offline_by_tag"] == dict(eng.ledger.offline.by_tag)
        assert srv_side["online_by_tag"] == dict(eng.ledger.online.by_tag)
        assert srv_side["offline_bytes"] > 0 and srv_side["online_bytes"] > 0

    # shared garbling cache: one slab per distinct netlist across ALL
    # sessions — 1 miss each on the first prep, hits from the other 3
    cache = st["garbling_cache"]
    assert cache["slabs"] == cache["distinct_netlists"] > 0
    assert cache["misses"] == cache["slabs"]
    assert cache["hits"] == 3 * cache["slabs"]

    for eng in engines:
        eng.close()
    loop.stop()
    gw.close()
    lst.close()


# ---------------------------------------------------------------------------
# teardown: a killed client returns its bundles, others are untouched
# ---------------------------------------------------------------------------


def test_gateway_kill_mid_session_returns_bundles():
    model = _model(seed=21)
    gw = PitGateway(model, S, impl="ref", max_sessions=4, pool_cap=4)
    rng = np.random.default_rng(22)

    victim = _inproc_engine(gw, seed=1)
    survivor = _inproc_engine(gw, seed=2)
    victim.preprocess(2)
    survivor.preprocess(1)
    x = rng.normal(0, 1, (S, D))
    victim.run(x)  # consumes 1 of its 2 bundles

    # kill: close both transports with no bye — the server sees the
    # peer vanish mid-session with a bundle still outstanding
    victim.offline.transport.close()
    victim.online.transport.close()
    _wait(lambda: gw.stats()["sessions_active"] == 1,
          what="victim session teardown")

    st = gw.stats()
    assert st["bundles_returned"] == 1  # the unconsumed one came back
    dead = [s for s in st["sessions"] if s["bundles_returned"] == 1]
    assert len(dead) == 1 and dead[0]["bundles_outstanding"] == 0

    # the surviving session is unaffected: its bundle is intact and runs
    y = survivor.run(x)
    sess = model.compile_session(S, impl="ref")
    assert np.array_equal(y, sess.run(x, sess.preprocess(1)[0]))
    survivor.close()
    gw.close()


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_gateway_kill_during_pipelined_refill_reclaims_exactly():
    """The kill lands *inside* a pipelined ``refill_async`` prep stream
    (not between requests): the in-flight prep must vanish without a
    trace — neither side committed it — and the reclaim returns exactly
    the prior unconsumed bundles. The refill thread dies loudly on the
    injected reset, hence the warning filter."""
    from repro.net import Fault, FaultyTransport
    from repro.serve import NetPrivateServeEngine

    model = _model(seed=23)
    gw = PitGateway(model, S, impl="ref", max_sessions=4, pool_cap=8)
    rng = np.random.default_rng(24)

    # the victim's offline leg runs through a FaultyTransport so the
    # kill can be armed deterministically relative to the op counter
    off_c, off_s = InProcPipe.make_pair()
    on_c, on_s = InProcPipe.make_pair()
    gw.serve_transport(off_s, timeout=120)
    gw.serve_transport(on_s, timeout=120)
    ft = FaultyTransport(off_c)
    victim = NetPrivateServeEngine(ft, on_c, pool_target=2, seed=1,
                                   impl="ref", timeout=120)
    survivor = _inproc_engine(gw, seed=2)

    victim.preprocess(2)
    survivor.preprocess(1)
    x = rng.normal(0, 1, (S, D))
    victim.run(x)  # consumes 1 of the victim's 2 bundles

    ft.arm(Fault(ft.op + 4, "reset"))  # fires mid-prep-stream
    refill = victim.refill_async(1)
    refill.join(timeout=120)
    assert not refill.is_alive(), "refill thread hung on the kill"
    assert victim.pool_size() == 1, "failed refill must not grow the pool"

    # finish the crash: the online leg vanishes too, no bye
    victim.online.transport.close()
    _wait(lambda: gw.stats()["sessions_active"] == 1,
          what="victim session teardown")

    st = gw.stats()
    # exactly the unconsumed prior bundle came back; the interrupted
    # prep was never committed on either side (no phantom bundle, no
    # burn — only a mid-RUN interrupt burns)
    assert st["bundles_prepped"] == 3  # victim 2 + survivor 1
    assert st["bundles_returned"] == 1
    assert st["bundles_burned"] == 0
    assert st["bundles_consumed"] == 1
    assert st["bundles_prepped"] == (
        st["bundles_consumed"] + st["bundles_outstanding"]
        + st["bundles_returned"] + st["bundles_burned"])

    # the survivor is untouched and bit-identical
    y = survivor.run(x)
    sess = model.compile_session(S, impl="ref")
    assert np.array_equal(y, sess.run(x, sess.preprocess(1)[0]))
    survivor.close()
    gw.close()


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------


def test_gateway_session_cap_sheds_with_hint():
    model = _model(seed=31)
    gw = PitGateway(model, S, impl="ref", max_sessions=1)
    lst = TcpListener()
    loop = gw.serve_listener(lst, accept_timeout=0.2, timeout=60)

    eng = gateway_client("127.0.0.1", lst.port, seed=1, impl="ref",
                         timeout=60)
    with pytest.raises(BundlePoolEmpty) as ei:
        gateway_client("127.0.0.1", lst.port, seed=2, impl="ref",
                       timeout=60)
    assert ei.value.scope == "session"
    assert ei.value.retry_after_s is not None and ei.value.retry_after_s > 0
    assert gw.stats()["sessions_shed"] == 1

    # the admitted session keeps working after the shed
    rng = np.random.default_rng(32)
    x = rng.normal(0, 1, (S, D))
    eng.preprocess(1)
    y = eng.run(x)
    sess = model.compile_session(S, impl="ref")
    assert np.array_equal(y, sess.run(x, sess.preprocess(1)[0]))

    eng.close()
    loop.stop()
    gw.close()
    lst.close()


# ---------------------------------------------------------------------------
# stats()/metrics() thread-safety: consistent snapshots under churn
# ---------------------------------------------------------------------------


def test_gateway_stats_metrics_consistent_under_hammer():
    """N client threads mutate the gateway (admits, preps, runs,
    teardowns) while a reader polls ``stats()``/``metrics()`` in a tight
    loop. Every snapshot must be internally consistent (counters taken
    under the gateway lock, per-session summaries under each session
    lock) and the metrics counters monotonic across polls — a torn read
    shows up as a violated identity or a counter going backwards."""
    model = _model(seed=51)
    gw = PitGateway(model, S, impl="ref", max_sessions=8, pool_cap=8)
    stop = threading.Event()
    problems = []
    polls = [0]
    counter_keys = {"sessions_admitted", "sessions_shed", "prep_sheds",
                    "sessions_resumed", "leases_expired",
                    "bundles_prepped", "bundles_consumed",
                    "bundles_returned", "bundles_burned",
                    "garbling_cache_hits", "garbling_cache_misses"}
    gauge_keys = {"sessions_active", "sessions_parked",
                  "bundles_outstanding", "prep_inflight",
                  "prep_ewma_s", "bundles_per_s", "elapsed_s"}

    def reader():
        last = None
        while not stop.is_set():
            st = gw.stats()
            m = gw.metrics()
            try:
                assert m["schema"] == "pit.gateway.v1"
                assert set(m["counters"]) == counter_keys  # stable schema
                assert set(m["gauges"]) == gauge_keys
                assert isinstance(m["spans"], dict)
                assert st["sessions_active"] <= st["sessions_admitted"]
                # every prepped bundle is outstanding, consumed,
                # returned, or burned — an identity only a consistent
                # snapshot keeps (burn accounting holds it mid-run too)
                assert st["bundles_prepped"] == (
                    st["bundles_consumed"] + st["bundles_outstanding"]
                    + st["bundles_burned"]
                    + sum(s["bundles_returned"] for s in st["sessions"]))
                if last is not None:
                    for k in counter_keys:
                        assert m["counters"][k] >= last[k], \
                            f"counter {k} went backwards"
                last = m["counters"]
                polls[0] += 1
            except AssertionError as e:
                problems.append(str(e))
                stop.set()
                return

    rd = threading.Thread(target=reader)
    rd.start()

    rng = np.random.default_rng(52)
    xs = [rng.normal(0, 1, (S, D)) for _ in range(3)]
    errs = []

    def client(i):
        try:
            eng = _inproc_engine(gw, seed=60 + i)
            eng.preprocess(1)
            eng.run(xs[i])
            eng.close()  # clean teardown churns the session table too
        except Exception as e:
            errs.append((i, e))

    threads = [threading.Thread(target=client, args=(i,)) for i in range(3)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=240)
    stop.set()
    rd.join(timeout=30)
    assert not errs, errs
    assert not problems, problems
    assert polls[0] > 10, "reader barely ran — hammer proved nothing"
    st = gw.stats()
    assert st["sessions_admitted"] == 3
    assert st["bundles_consumed"] == 3
    gw.close()


def test_gateway_bounded_pool_sheds_before_garbling():
    model = _model(seed=41)
    gw = PitGateway(model, S, impl="ref", max_sessions=2, pool_cap=1)
    eng = _inproc_engine(gw, seed=1)

    assert eng.preprocess(1) == 1  # at the cap
    c2s_after_first = eng.ledger.offline.client_to_server
    with pytest.raises(BundlePoolEmpty) as ei:
        eng.preprocess(1)  # would exceed pool_cap=1 -> typed shed
    assert ei.value.scope == "prep"
    assert ei.value.retry_after_s is not None and ei.value.retry_after_s > 0
    # shed before the expensive work: no offline PROTO bytes moved (the
    # refused prep cost one CONTROL round trip, nothing garbled)
    assert eng.ledger.offline.client_to_server == c2s_after_first
    assert gw.stats()["prep_sheds"] == 1

    # consuming the outstanding bundle frees capacity again
    rng = np.random.default_rng(42)
    eng.run(rng.normal(0, 1, (S, D)))
    assert eng.preprocess(1) == 1
    eng.close()
    gw.close()
