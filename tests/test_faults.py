"""Fault injection + resilient sessions (ISSUE 10).

Covers the TransportTimeout/TransportClosed split, deterministic seeded
fault schedules (same seed => same faults => same outcome on InProcPipe
AND TcpTransport), burn-on-interrupt bundle semantics, reconnect/resume
against a lease-holding gateway, and a seeded chaos sweep where every
schedule either completes bit-identical or fails with a typed error —
no hangs, no bundle reuse, no secret bytes on error/CONTROL frames.
"""

import re
import socket
import struct
import threading
import time

import numpy as np
import pytest

from repro.config import PrivacyConfig
from repro.core.engine import PrivateTransformer, random_weights
from repro.net import (
    Deadlines,
    Fault,
    FaultPlan,
    FaultSchedule,
    FaultyTransport,
    GarblerEndpoint,
    InProcPipe,
    PitNetServer,
    ResilientClient,
    RetryPolicy,
    SessionLost,
    TcpListener,
    TcpTransport,
    TransportClosed,
    TransportTimeout,
)
from repro.net import wire as W
from repro.serve import BundlePoolEmpty, PitGateway

D, HEADS, DFF, S = 8, 2, 16, 4


def _model(seed=0):
    rng = np.random.default_rng(seed)
    weights = random_weights(rng, D, DFF, 1)
    pcfg = PrivacyConfig(he_poly_n=256, he_num_primes=3, he_t_bits=40,
                         frac_bits=6)
    return PrivateTransformer(pcfg, D, HEADS, DFF, weights, seed=seed)


# ---------------------------------------------------------------------------
# the timeout/closed split, per transport
# ---------------------------------------------------------------------------


def test_inproc_recv_timeout_is_typed():
    a, b = InProcPipe.make_pair()
    with pytest.raises(TransportTimeout):
        a.recv(timeout=0.05)
    # the split subclasses: every legacy `except TransportClosed` path
    # still catches a timeout
    assert issubclass(TransportTimeout, TransportClosed)
    b.close()
    with pytest.raises(TransportClosed) as ei:
        a.recv(timeout=1.0)
    assert not isinstance(ei.value, TransportTimeout)  # closed, not slow


def test_tcp_recv_timeout_is_typed():
    lst = TcpListener()
    raw = socket.create_connection(("127.0.0.1", lst.port))
    srv = lst.accept(timeout=5)
    # silence on a frame boundary: recoverable slowness
    with pytest.raises(TransportTimeout):
        srv.recv(timeout=0.05)
    # a torn length prefix: 2 of 4 header bytes then silence — framing
    # is lost, so this must be a hard close, not a retryable timeout
    raw.sendall(struct.pack(">I", 64)[:2])
    time.sleep(0.05)
    with pytest.raises(TransportClosed) as ei:
        srv.recv(timeout=0.2)
    assert not isinstance(ei.value, TransportTimeout)
    raw.close()
    srv.close()
    lst.close()


def test_deadlines_per_phase():
    dl = Deadlines(hello_s=1.0, online_s=3.0, default_s=9.0)
    assert dl.for_phase("hello") == 1.0
    assert dl.for_phase("online") == 3.0
    assert dl.for_phase("offline") == 9.0  # unset phase -> default
    assert dl.for_phase("idle") == 9.0
    u = Deadlines.uniform(7.0)
    assert all(u.for_phase(p) == 7.0
               for p in ("hello", "offline", "online", "idle"))


# ---------------------------------------------------------------------------
# schedules: seeded, deterministic, replayable
# ---------------------------------------------------------------------------


def test_schedule_from_seed_deterministic():
    for seed in range(20):
        s1 = FaultSchedule.from_seed(seed, n_faults=3, horizon=48)
        s2 = FaultSchedule.from_seed(seed, n_faults=3, horizon=48)
        assert s1 == s2
        assert all(f.op >= 2 for f in s1.faults)  # first_op spared
        assert all(f.kind in ("reset", "stall", "torn", "dup")
                   for f in s1.faults)
    assert FaultSchedule.from_seed(1) != FaultSchedule.from_seed(2)


def test_fault_plan_goes_clean_after_faulty_conns():
    plan = FaultPlan(seed=3, faulty_conns=2, n_faults=2)
    assert len(plan.schedule_for(0)) == 2
    assert len(plan.schedule_for(1)) == 2
    assert len(plan.schedule_for(2)) == 0  # chaos runs terminate
    assert plan.schedule_for(0) == FaultPlan(
        seed=3, faulty_conns=2, n_faults=2).schedule_for(0)


def test_stall_outliving_timeout_raises_transport_timeout():
    a, b = InProcPipe.make_pair()
    ft = FaultyTransport(a, FaultSchedule((Fault(0, "stall", 5.0),)))
    b.send(b"late frame")
    t0 = time.monotonic()
    with pytest.raises(TransportTimeout):
        ft.recv(timeout=0.1)  # stall outlives the deadline
    assert time.monotonic() - t0 < 1.0  # slept the timeout, not the stall
    assert ft.injected == [(0, "stall")]
    a.close()
    b.close()


def test_short_stall_delivers_late():
    a, b = InProcPipe.make_pair()
    ft = FaultyTransport(a, FaultSchedule((Fault(0, "stall", 0.05),)))
    b.send(b"frame")
    assert ft.recv(timeout=1.0) == b"frame"
    a.close()
    b.close()


def test_dup_fault_delivers_frame_twice():
    a, b = InProcPipe.make_pair()
    ft = FaultyTransport(a, FaultSchedule((Fault(0, "dup"),)))
    b.send(b"once")
    b.send(b"next")
    assert ft.recv(timeout=1.0) == b"once"
    assert ft.recv(timeout=1.0) == b"once"  # the duplicate delivery
    assert ft.recv(timeout=1.0) == b"next"
    a.close()
    b.close()


# ---------------------------------------------------------------------------
# cross-transport determinism: same schedule, same faults, same outcome
# ---------------------------------------------------------------------------


def _session_endpoints(model, kind, schedule, *, record=False):
    """(faulty client endpoint, cleanup) over transport ``kind``."""
    srv = PitNetServer(model, S, impl="ref")
    if kind == "inproc":
        a, b = InProcPipe.make_pair()
        srv.serve_transport(b, timeout=60)
        ft = FaultyTransport(a, schedule, record_frames=record)
        cli = GarblerEndpoint(ft, seed=7, impl="ref", timeout=60)
        return cli, ft, lambda: a.close()
    lst = TcpListener()
    loop = srv.serve_tcp(lst, timeout=60)
    ft = FaultyTransport(TcpTransport.connect("127.0.0.1", lst.port),
                         schedule, record_frames=record)
    cli = GarblerEndpoint(ft, seed=7, impl="ref", timeout=60)
    loop.wait_accepted(1, timeout=30)

    def cleanup():
        ft.close()
        lst.close()

    return cli, ft, cleanup


def _faulted_prep(model, kind, schedule):
    cli, ft, cleanup = _session_endpoints(model, kind, schedule)
    try:
        cli.preprocess(1)
        return "ok", list(ft.injected)
    except (TransportClosed, W.WireError, Exception) as e:
        return type(e).__name__, list(ft.injected)
    finally:
        cleanup()


@pytest.mark.parametrize("fault", [Fault(5, "reset"), Fault(7, "torn")])
def test_fatal_fault_identical_on_inproc_and_tcp(fault):
    model = _model(seed=61)
    schedule = FaultSchedule((fault,))
    out_inproc = _faulted_prep(model, "inproc", schedule)
    out_tcp = _faulted_prep(model, "tcp", schedule)
    # the endpoints walk the protocol in lockstep, so the k-th transport
    # op is the same op on both transports: identical fault log AND
    # identical typed outcome
    assert out_inproc == out_tcp
    assert out_inproc[1] == [(fault.op, fault.kind)]
    assert out_inproc[0] != "ok"


def test_benign_stall_bit_identical_on_inproc_and_tcp():
    model = _model(seed=62)
    rng = np.random.default_rng(63)
    x = rng.normal(0, 1, (S, D))
    sess = model.compile_session(S, impl="ref")
    y_ref = sess.run(x, sess.preprocess(1)[0])
    schedule = FaultSchedule((Fault(4, "stall", 0.05),))
    for kind in ("inproc", "tcp"):
        cli, ft, cleanup = _session_endpoints(model, kind, schedule)
        try:
            cli.preprocess(1)
            y = cli.run(x)
            assert np.array_equal(y, y_ref), kind
            assert ft.injected == [(4, "stall")], kind
        finally:
            cleanup()


# ---------------------------------------------------------------------------
# resilient client: reconnect, resume, burn-on-interrupt
# ---------------------------------------------------------------------------


def _gateway_identity(st):
    assert st["bundles_prepped"] == (
        st["bundles_consumed"] + st["bundles_outstanding"]
        + st["bundles_returned"] + st["bundles_burned"]), st


def test_resilient_reconnect_resumes_and_burns():
    """A forced reset mid-run: the interrupted bundle is burned on both
    sides, the client reconnects into the SAME session (lease held), and
    the retried run — on a fresh bundle — is bit-identical."""
    model = _model(seed=71)
    gw = PitGateway(model, S, impl="ref", max_sessions=4, pool_cap=4,
                    lease_s=30.0)
    dl = Deadlines.uniform(15.0)
    schedules = {1: FaultSchedule((Fault(8, "reset"),))}  # online leg
    conns = [0]

    def connect():
        c, s = InProcPipe.make_pair()
        gw.serve_transport(s, deadlines=dl)
        i = conns[0]
        conns[0] += 1
        return FaultyTransport(c, schedules.get(i, FaultSchedule()))

    cli = ResilientClient(connect, seed=5, impl="ref",
                          policy=RetryPolicy(attempts=6, base_s=0.02),
                          deadlines=dl)
    rng = np.random.default_rng(72)
    x = rng.normal(0, 1, (S, D))
    cli.preprocess(2)
    y = cli.run(x)
    sess = model.compile_session(S, impl="ref")
    y_ref = sess.run(x, sess.preprocess(1)[0])
    assert np.array_equal(y, y_ref)

    cst = cli.stats()
    assert cst["reconnects"] == 1 and cst["resume_handshakes"] == 1
    assert cst["bundles_burned"] == 1
    st = gw.stats()
    assert st["sessions_resumed"] == 1 and st["bundles_burned"] == 1
    assert [s["epoch"] for s in st["sessions"]] == [1]
    _gateway_identity(st)

    # the resumed session keeps serving bit-identically
    assert np.array_equal(cli.run(x), y_ref)
    cli.close()  # clean bye: immediate reclaim despite the lease
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and gw.stats()["sessions_active"]:
        time.sleep(0.05)
    st = gw.stats()
    assert st["sessions_active"] == 0 and st["sessions_parked"] == 0
    _gateway_identity(st)
    gw.close()


def test_interrupted_prep_retried_with_fresh_ids():
    """A reset mid-prep: nothing is committed on either side, and the
    retry lands new bundle ids — no id collision, no phantom bundles."""
    model = _model(seed=73)
    gw = PitGateway(model, S, impl="ref", max_sessions=4, pool_cap=4,
                    lease_s=30.0)
    dl = Deadlines.uniform(15.0)
    schedules = {0: FaultSchedule((Fault(6, "reset"),))}  # offline leg
    conns = [0]

    def connect():
        c, s = InProcPipe.make_pair()
        gw.serve_transport(s, deadlines=dl)
        i = conns[0]
        conns[0] += 1
        return FaultyTransport(c, schedules.get(i, FaultSchedule()))

    cli = ResilientClient(connect, seed=6, impl="ref",
                          policy=RetryPolicy(attempts=6, base_s=0.02),
                          deadlines=dl)
    ids = cli.preprocess(1)
    assert len(ids) == 1 and cli.pool_size() == 1
    st = gw.stats()
    assert st["bundles_prepped"] == 1  # the torn prep never committed
    assert st["bundles_burned"] == 0  # prep interruption burns nothing
    _gateway_identity(st)
    rng = np.random.default_rng(74)
    x = rng.normal(0, 1, (S, D))
    sess = model.compile_session(S, impl="ref")
    assert np.array_equal(cli.run(x), sess.run(x, sess.preprocess(1)[0]))
    cli.close()
    gw.close()


def test_lease_expiry_reclaims_and_surfaces_session_lost():
    """A crashed client that stays away past its lease loses the
    session: bundles return to the identity, and a late resume attempt
    fails typed (SessionLost), never silently rebinding."""
    model = _model(seed=75)
    gw = PitGateway(model, S, impl="ref", max_sessions=4, pool_cap=4,
                    lease_s=0.2)
    dl = Deadlines.uniform(15.0)

    def connect():
        c, s = InProcPipe.make_pair()
        gw.serve_transport(s, deadlines=dl)
        return c

    cli = ResilientClient(connect, seed=7, impl="ref",
                          policy=RetryPolicy(attempts=3, base_s=0.02),
                          deadlines=dl)
    cli.preprocess(1)
    # crash: both transports vanish, no bye
    cli.offline.transport.close()
    cli.online.transport.close()
    cli._teardown()

    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and not gw.stats()["leases_expired"]:
        time.sleep(0.05)
    st = gw.stats()
    assert st["leases_expired"] == 1 and st["sessions_parked"] == 0
    assert st["bundles_returned"] == 1  # the parked bundle came home
    _gateway_identity(st)

    rng = np.random.default_rng(76)
    with pytest.raises(SessionLost):
        cli.run(rng.normal(0, 1, (S, D)))
    cli.close()
    gw.close()


# ---------------------------------------------------------------------------
# seeded chaos sweep: every schedule completes bit-identical or fails typed
# ---------------------------------------------------------------------------

#: the only strings an error CONTROL frame may carry: a class name plus
#: a fixed parenthetical — never str(e), payload bytes, or tracebacks
_ERROR_WHITELIST = re.compile(
    r"^[A-Za-z_][A-Za-z0-9_]* \((idle deadline exceeded|"
    r"request deadline exceeded|see evaluator-side log)\)$")


def _audit_frames(plan):
    """Every decodable CONTROL frame that crossed a faulty transport:
    error payloads are class-name-only, per the secretflow discipline."""
    audited = 0
    for ft in plan.transports:
        for _direction, frame in ft.frame_log:
            try:
                msg = W.decode_frame(frame)
            except Exception:
                continue  # torn frames are expected to be undecodable
            if msg.kind != W.KIND_CONTROL:
                continue
            audited += 1
            if msg.tag == "error":
                assert isinstance(msg.payload, str), msg.payload
                assert _ERROR_WHITELIST.match(msg.payload), msg.payload
    return audited


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_chaos_sweep_typed_or_bit_identical():
    """~Dozen seeded schedules through a gateway run: each either
    completes bit-identical or fails with a typed error — no hangs, no
    bundle reuse, class-name-only error frames. Server endpoint threads
    are expected to die loudly on injected desyncs (duplicate frames
    land as unexpected CONTROL tags), hence the warning filter."""
    model = _model(seed=81)
    rng = np.random.default_rng(82)
    x = rng.normal(0, 1, (S, D))
    sess = model.compile_session(S, impl="ref")
    y_ref = sess.run(x, sess.preprocess(1)[0])
    dl = Deadlines.uniform(20.0)

    outcomes = {}
    audited_total = 0
    for seed in range(12):
        gw = PitGateway(model, S, impl="ref", max_sessions=4, pool_cap=4,
                        lease_s=30.0)
        plan = FaultPlan(seed=seed, faulty_conns=2, n_faults=1,
                         first_op=2, horizon=40, stall_s=0.05,
                         record_frames=True)

        def connect():
            c, s = InProcPipe.make_pair()
            gw.serve_transport(s, deadlines=dl)
            return plan.wrap(c)

        cli = ResilientClient(
            connect, seed=seed, impl="ref",
            policy=RetryPolicy(attempts=6, base_s=0.01, max_s=0.05,
                               seed=seed),
            deadlines=dl)
        try:
            cli.preprocess(1)
            y = cli.run(x)
            outcomes[seed] = ("ok" if np.array_equal(y, y_ref)
                              else "DIVERGED")
        except BundlePoolEmpty:
            outcomes[seed] = "BundlePoolEmpty"
        except TransportClosed as e:
            outcomes[seed] = type(e).__name__  # typed, incl. SessionLost
        finally:
            try:
                cli.close()
            except (TransportClosed, OSError):
                pass

        # replayability: the plan re-derives the exact schedules it ran
        for i, ft in enumerate(plan.transports):
            assert ft.schedule == plan.schedule_for(i), (seed, i)
        # the bundle identity holds whatever the faults did
        _gateway_identity(gw.stats())
        audited_total += _audit_frames(plan)
        gw.close()

    assert all(v != "DIVERGED" for v in outcomes.values()), outcomes
    allowed = {"ok", "BundlePoolEmpty", "TransportClosed",
               "TransportTimeout", "SessionLost"}
    assert set(outcomes.values()) <= allowed, outcomes
    # the sweep must actually exercise recovery, not sail through twelve
    # empty schedules — and the frame audit must have seen real traffic
    assert sum(1 for v in outcomes.values() if v == "ok") >= 6, outcomes
    assert audited_total > 0


def test_chaos_schedules_replay_identically():
    """Same seed => byte-for-byte the same fault schedule objects, the
    determinism the sweep's outcomes rest on."""
    for seed in range(12):
        p1 = FaultPlan(seed=seed, faulty_conns=2, n_faults=1, horizon=40)
        p2 = FaultPlan(seed=seed, faulty_conns=2, n_faults=1, horizon=40)
        for i in range(4):
            assert p1.schedule_for(i) == p2.schedule_for(i)
