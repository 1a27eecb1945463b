"""Tracing + metrics subsystem (``repro.obs``): tracer unit behavior,
Chrome trace_event export validity, the span-backed ``Stats.phase``
unification, and the ledger <-> trace reconciliation contract — on a real
two-party TCP run, the per-(phase, tag) byte sums of the ``wire:seg``
trace events must equal the :class:`~repro.net.party.WireLedger` per-tag
totals *exactly*, on both wire versions.
"""

import importlib.util
import json
import threading
import time
from collections import defaultdict
from pathlib import Path

import numpy as np
import pytest

from repro import obs
from repro.config import PrivacyConfig
from repro.core.engine import PrivateTransformer, random_weights
from repro.core.protocol import Stats

ROOT = Path(__file__).resolve().parents[1]
D, HEADS, DFF, S = 8, 2, 16, 4


def _load_trace_check():
    """The CI artifact validator, loaded from scripts/ (not a package)."""
    spec = importlib.util.spec_from_file_location(
        "trace_check", ROOT / "scripts" / "trace_check.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _model(seed=0):
    rng = np.random.default_rng(seed)
    weights = random_weights(rng, D, DFF, 1)
    pcfg = PrivacyConfig(he_poly_n=256, he_num_primes=3, he_t_bits=40,
                         frac_bits=6)
    return PrivateTransformer(pcfg, D, HEADS, DFF, weights, seed=seed)


@pytest.fixture
def tracer():
    tr = obs.enable()
    yield tr
    obs.disable()


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------


def test_span_nesting_builds_paths(tracer):
    with obs.span("a"):
        with obs.span("b", n=1):
            with obs.span("c"):
                pass
        with obs.span("b"):
            pass
    paths = [sp.path for sp in tracer.finished_spans()]
    assert sorted(paths) == ["a", "a/b", "a/b", "a/b/c"]
    rep = tracer.report()
    assert rep["a/b"]["count"] == 2
    assert rep["a"]["count"] == 1
    assert rep["a"]["total_s"] >= rep["a/b"]["total_s"] > 0
    assert rep["a/b"]["max_s"] >= rep["a/b"]["mean_s"]


def test_span_attrs_reject_payloads(tracer):
    with pytest.raises(TypeError):
        obs.span("x", labels=np.arange(3))
    with pytest.raises(TypeError):
        obs.span("x", data=b"\x00\x01")
    with pytest.raises(TypeError):
        obs.instant("x", seg=[1, 2])
    with obs.span("x") as sp:
        with pytest.raises(TypeError):
            sp.set(arr=np.zeros(2))
        sp.set(bytes=16, tag="shares", ok=True, frac=0.5)  # scalars pass
    assert tracer.finished_spans()[-1].attrs["bytes"] == 16


def test_null_tracer_is_shared_noop():
    assert obs.current() is obs.NULL_TRACER
    s1, s2 = obs.span("a", n=1), obs.span("b")
    assert s1 is s2  # one preallocated object, no per-call allocation
    assert s1.elapsed_s == 0.0
    assert s1.set(x=1) is s1 and s1.close() is s1
    with obs.span("c"):
        pass
    obs.instant("i", n=2)
    assert obs.current().finished_spans() == []
    assert obs.current().report() == {}
    with pytest.raises(RuntimeError):
        obs.current().export("/tmp/never.json")


def test_timer_measures_with_tracing_off_and_on(tracer):
    obs.disable()
    sp = obs.timer("t", n=1)
    time.sleep(0.01)
    assert sp.close().elapsed_s >= 0.01  # real measurement, unrecorded
    assert obs.current().finished_spans() == []

    obs.install(tracer)
    with obs.timer("t2") as sp2:
        time.sleep(0.001)
    assert sp2.elapsed_s > 0
    assert [s.name for s in tracer.finished_spans()] == ["t2"]


def test_stats_phase_is_span_backed(tracer):
    """The Stats.phase timing path and the trace are the same clock:
    one outermost block == one recorded span == one t_s accumulation."""
    st = Stats()
    with st.phase("offline"):
        with st.phase("offline"):  # re-entrant: inner block is free
            time.sleep(0.005)
        with obs.span("op:linear"):
            pass
    assert st.t_offline_s >= 0.005
    rep = tracer.report()
    assert rep["offline"]["count"] == 1
    assert abs(rep["offline"]["total_s"] - st.t_offline_s) < 1e-9
    assert rep["offline/op:linear"]["count"] == 1  # ops nest under phase


def test_tracer_threads_isolated_stacks(tracer):
    barrier = threading.Barrier(8)  # all 8 alive at once: distinct tids

    def worker(i):
        barrier.wait(timeout=30)
        with obs.span("outer", worker=i):
            with obs.span("inner"):
                pass

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=30)
    spans = tracer.finished_spans()
    assert len(spans) == 16
    # per-thread stacks: every inner nested under ITS thread's outer
    for sp in spans:
        if sp.name == "inner":
            assert sp.path == "outer/inner"
    assert len({sp._tid for sp in spans}) == 8


def test_export_chrome_schema(tracer, tmp_path):
    with obs.span("parent", n=2):
        with obs.span("child"):
            obs.instant("tick", bytes=4)
    out = tmp_path / "t.json"
    tracer.export(str(out))
    doc = json.loads(out.read_text())
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    evs = doc["traceEvents"]
    assert [e["ph"] for e in evs].count("B") == 2
    assert [e["ph"] for e in evs].count("E") == 2
    inst = [e for e in evs if e["ph"] == "i"]
    assert len(inst) == 1 and inst[0]["s"] == "t"
    assert all(evs[i]["ts"] <= evs[i + 1]["ts"] for i in range(len(evs) - 1))
    # the artifact validator CI runs must agree it is clean + balanced
    assert _load_trace_check().check_events(doc) == []


def test_trace_check_catches_bad_traces():
    tc = _load_trace_check()
    base = {"cat": "x", "ts": 1.0, "pid": 1, "tid": 1}
    # unbalanced: B without E
    doc = {"traceEvents": [{"name": "a", "ph": "B", **base}]}
    assert any("unclosed" in p for p in tc.check_events(doc))
    # mismatched close
    doc = {"traceEvents": [{"name": "a", "ph": "B", **base},
                           {"name": "b", "ph": "E", **base}]}
    assert any("closes" in p for p in tc.check_events(doc))
    # secret-looking attribute key
    doc = {"traceEvents": [{"name": "a", "ph": "i", **base,
                            "args": {"input_labels": 3}}]}
    assert any("secret-looking" in p for p in tc.check_events(doc))
    # payload-shaped attribute value
    doc = {"traceEvents": [{"name": "a", "ph": "i", **base,
                            "args": {"v": [1, 2, 3]}}]}
    assert any("payload-shaped" in p for p in tc.check_events(doc))
    assert tc.check_events({"traceEvents": []}) == []


# ---------------------------------------------------------------------------
# the reconciliation contract: trace wire:seg sums == WireLedger, exactly
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("wire_version", [1, 2])
def test_trace_reconciles_with_wire_ledger_tcp(tracer, tmp_path,
                                               wire_version):
    """Full two-party TCP run with tracing on: for every (phase, tag),
    the byte attrs of the ``wire:seg`` trace events sum to the
    :class:`WireLedger` per-tag totals exactly — once over the sender's
    ``wire.send`` emissions and once over the receiver's ``wire.recv``
    emissions (both parties share the process-global tracer here)."""
    from repro.net import (GarblerEndpoint, PitNetServer, TcpListener,
                           TcpTransport)

    model = _model(seed=17)
    rng = np.random.default_rng(18)
    x = rng.normal(0, 1, (S, D))
    srv = PitNetServer(model, S, impl="ref")
    lst = TcpListener()
    loop = srv.serve_tcp(lst, timeout=300)
    cli = GarblerEndpoint(TcpTransport.connect("127.0.0.1", lst.port),
                          seed=19, impl="ref", timeout=300,
                          wire_version=wire_version)
    assert loop.wait_accepted(1, timeout=30)
    cli.preprocess(1)
    y = cli.run(x)
    assert cli.shared.negotiated_version == wire_version
    assert np.isfinite(y).all()
    cli.close()
    lst.close()

    sent = {"offline": defaultdict(int), "online": defaultdict(int)}
    rcvd = {"offline": defaultdict(int), "online": defaultdict(int)}
    for name, _ts, _tid, attrs in tracer.finished_instants():
        if name != "wire:seg":
            continue
        side = sent if attrs["dir"] == "send" else rcvd
        side[attrs["phase"]][attrs["tag"]] += attrs["bytes"]

    led = cli.shared.ledger
    for phase, chan in (("offline", led.offline), ("online", led.online)):
        want = dict(chan.by_tag)
        assert dict(sent[phase]) == want, f"v{wire_version} {phase} send"
        assert dict(rcvd[phase]) == want, f"v{wire_version} {phase} recv"
    # and the server-side ledger tells the same story
    sled = srv.shared.ledger
    assert dict(sent["offline"]) == dict(sled.offline.by_tag)
    assert dict(sent["online"]) == dict(sled.online.by_tag)

    # structural nesting: protocol op spans live under the phase spans
    paths = {sp.path for sp in tracer.finished_spans()}
    assert "offline" in paths and "online" in paths
    assert any(p.startswith("online/op:") for p in paths), sorted(paths)
    # client-side garbling, one slab per netlist under offline
    assert "offline/slab/garble" in paths
    assert any(p.startswith("online/wire.") for p in paths)

    # the host work inside both parties' legs, each on its party's
    # thread (the garbler drives from this thread, the evaluator serves
    # from its own) under the right parent
    me = threading.get_ident()
    spans = tracer.finished_spans()
    garbler = {sp.path for sp in spans if sp._tid == me}
    evaluator = {sp.path for sp in spans if sp._tid != me}
    for path in ("offline/slab", "offline/slab/gc.fetch",
                 "offline/slab/tables.pack", "offline/he.offline",
                 "online/op:trunc/gc.encode", "online/op:trunc/ot.respond",
                 "online/op:beaver_matmul/share.matmul"):
        assert path in garbler, (path, sorted(garbler))
    for path in ("offline/tables.unpack", "offline/he.offline",
                 "offline/he.offline/share.matmul",
                 "online/op:linear/share.matmul",
                 "online/op:beaver_matmul/share.matmul",
                 "online/op:trunc/gc.encode", "online/op:trunc/ot.receive",
                 "online/op:trunc/gc.upload", "online/op:trunc/gc.fetch",
                 "online/op:trunc/gc.decode"):
        assert path in evaluator, (path, sorted(evaluator))
    v2_only = {"offline/tables.send": garbler,
               "offline/labels.stream": evaluator,
               "online/op:gc_apply/ot.extend": evaluator}
    for path, side in v2_only.items():
        assert (path in side) == (wire_version == 2), path
    assert any(p.endswith("/ot.base") for p in garbler) == (wire_version == 2)
    assert any(p.endswith("/ot.base") for p in evaluator) == \
        (wire_version == 2)
    assert not any("gc_exec." in p for p in garbler | evaluator)
    roots = [sp for sp in spans if sp.path in ("offline", "online")]
    assert len(roots) == 4
    assert all(isinstance(sp.attrs["bundle"], int) for sp in roots)

    # gc.upload carries each GC op's tables (32 B per AND) and labels
    # (16 B per input and constant wire), counted here from the netlists
    from repro.core.plan import GC_KINDS
    from repro.core.session import gc_net_for
    plan, prot = srv.shared.plan, srv.shared.protocol
    want = []
    for op in plan.ops:
        if op.kind in GC_KINDS:
            net = gc_net_for(prot, op)
            wires = (len(net.garbler_inputs) + len(net.evaluator_inputs)
                     + len(net.const_bits))
            want.append(plan.gc_instances(op)
                        * (32 * max(net.and_count, 1) + 16 * wires))
    uploads = sorted((sp for sp in spans if sp.name == "gc.upload"),
                     key=lambda sp: sp.t0_ns)
    assert [sp.attrs["bytes"] for sp in uploads] == want

    # every attribute is a plain scalar, never an array or numpy scalar
    for sp in spans:
        for k, v in sp.attrs.items():
            assert type(v) in (int, float, str, bool), (sp.path, k, v)

    # the exported artifact passes the CI validator end to end
    out = tmp_path / f"recon_v{wire_version}.json"
    tracer.export(str(out))
    doc = json.loads(out.read_text())
    assert _load_trace_check().check_events(doc) == []
    n_segs = sum(1 for e in doc["traceEvents"]
                 if e["ph"] == "i" and e["name"] == "wire:seg")
    assert n_segs == sum(1 for nm, *_ in tracer.finished_instants()
                         if nm == "wire:seg")
