"""Two-party runtime benchmark: rounds / bytes / wall-clock latency of
end-to-end private inference over real transports vs the metered-sim
prediction.

Measurements per transport (``InProcPipe``, loopback TCP):

* **parity** — the revealed output must be bit-identical to the
  in-process ``PiTSession.run`` path, and the per-phase wire ledger
  (payload bytes by tag) must equal the metered ``Channel`` oracle
  exactly (framing + sim-sideband overhead reported separately).
* **latency** — wall-clock offline (preprocess) and online (run), plus
  the oracle's LAN-model prediction (``Channel.time_s``: 9.6 Gb/s,
  0.165 ms) for the same byte/round counts.
* **pipelining** — with a dedicated offline endpoint pair
  (``NetPrivateServeEngine``), online serving proceeds while a
  bandwidth-shaped refill streams in the background; the benchmark
  records that the online request completed while refill traffic was in
  flight.
* **gateway** — N concurrent client sessions behind one ``PitGateway``
  accept loop: sessions served, shared-garbling-cache hits (one slab
  per distinct netlist for all clients), aggregate bundles/sec. On the
  full config this phase runs the reduced smoke model (noted in the
  JSON) so the 3-client fan-out doesn't dominate the bench wall-clock.
* **wire v1 vs v2** — endpoints negotiate wire v2 (PRG-seeded label
  streams, delta-encoded table batches, IKNP OT, round coalescing); the
  report carries both versions' oracle byte/round counts, the offline
  byte reduction, the coalesced round count
  (``rounds_after_coalescing`` < raw metered messages), per-phase
  direction-flip counts, and the LAN-model offline speedup computed
  with the *measured* post-coalescing rounds.

``python benchmarks/bench_net.py`` writes ``BENCH_net.json`` at the repo
root; ``--smoke`` (CI / ``benchmarks/run.py``) runs the tiny config and
asserts parity + ledger equality only; ``--check`` re-derives the
smoke-config oracle and fails on a >20% wire-byte regression against
the committed JSON (the net ratchet ``benchmarks/run.py --check``
runs in CI).
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np

SMOKE = {"d": 8, "heads": 2, "d_ff": 16, "S": 4, "layers": 1,
         "poly_n": 256, "primes": 3, "t_bits": 40, "frac": 6}
FULL = {"d": 16, "heads": 2, "d_ff": 32, "S": 8, "layers": 1,
        "poly_n": 256, "primes": 3, "t_bits": 40, "frac": 6}
# gateway fan-out point: mux/cache behavior is model-size independent,
# so the 3 concurrent clients run the smallest valid config
GATEWAY_CFG = {"d": 8, "heads": 2, "d_ff": 16, "S": 2, "layers": 1,
               "poly_n": 256, "primes": 3, "t_bits": 40, "frac": 6}


def _model(cfg):
    import jax

    jax.config.update("jax_enable_x64", True)
    from repro.config import PrivacyConfig
    from repro.core.engine import PrivateTransformer, random_weights

    rng = np.random.default_rng(0)
    weights = random_weights(rng, cfg["d"], cfg["d_ff"], cfg["layers"])
    pcfg = PrivacyConfig(he_poly_n=cfg["poly_n"], he_num_primes=cfg["primes"],
                         he_t_bits=cfg["t_bits"], frac_bits=cfg["frac"])
    return PrivateTransformer(pcfg, cfg["d"], cfg["heads"], cfg["d_ff"],
                              weights, seed=0)


def _oracle(model, cfg, x, wire_version=1):
    """In-process metered session: the byte/round/latency oracle."""
    sess = model.compile_session(cfg["S"], impl="ref",
                                 wire_version=wire_version)
    bundles = sess.preprocess(1)
    y = sess.run(x, bundles[0])
    st = sess.stats
    return y, {
        "wire_version": wire_version,
        "offline_bytes": st.channel_offline.total,
        "online_bytes": st.channel_online.total,
        "offline_msgs": st.channel_offline.rounds,
        "online_msgs": st.channel_online.rounds,
        "offline_by_tag": dict(st.channel_offline.by_tag),
        "online_by_tag": dict(st.channel_online.by_tag),
        "lan_model_offline_s": st.channel_offline.time_s(),
        "lan_model_online_s": st.channel_online.time_s(),
    }


def _endpoints(model, cfg, kind):
    """(client, server, cleanup) over the requested transport kind."""
    from repro.net import (GarblerEndpoint, InProcPipe, PitNetServer,
                           TcpListener, TcpTransport)

    srv = PitNetServer(model, cfg["S"], impl="ref")
    if kind == "inproc":
        a, b = InProcPipe.make_pair()
        srv.serve_transport(b, timeout=600)
        cli = GarblerEndpoint(a, seed=7, impl="ref", timeout=600)
        return cli, srv, lambda: cli.close()
    lst = TcpListener()
    loop = srv.serve_tcp(lst, timeout=600)
    cli = GarblerEndpoint(TcpTransport.connect("127.0.0.1", lst.port),
                          seed=7, impl="ref", timeout=600)
    loop.wait_accepted(1, timeout=60)

    def cleanup():
        cli.close()
        lst.close()

    return cli, srv, cleanup


def _point(model, cfg, kind, x, y_ref, oracle):
    cli, srv, cleanup = _endpoints(model, cfg, kind)
    try:
        t0 = time.perf_counter()
        cli.preprocess(1)
        t_off = time.perf_counter() - t0
        t0 = time.perf_counter()
        y = cli.run(x)
        t_on = time.perf_counter() - t0
        assert np.array_equal(y, y_ref), \
            f"{kind}: output diverged from the in-process session"
        led = cli.shared.ledger
        assert led.offline.by_tag == oracle["offline_by_tag"], \
            f"{kind}: offline ledger != metered oracle"
        assert led.online.by_tag == oracle["online_by_tag"], \
            f"{kind}: online ledger != metered oracle"
        proto = led.offline.total + led.online.total
        overhead = led.frame_bytes - proto - led.sim_bytes \
            - led.control_bytes
        s = led.summary()
        return {
            "transport": kind,
            "wire_version": cli.shared.negotiated_version,
            "compression": cli.shared.negotiated_compression,
            "offline_s": round(t_off, 3),
            "online_s": round(t_on, 3),
            "offline_bytes": led.offline.total,
            "online_bytes": led.online.total,
            "sim_sideband_bytes": led.sim_bytes,
            "table_resid_bytes": led.resid_bytes,
            "control_bytes": led.control_bytes,
            "framing_overhead_bytes": overhead,
            "overhead_pct_of_proto": round(
                100.0 * (led.sim_bytes + led.control_bytes + overhead)
                / max(proto, 1), 3),
            "wire_dir_flips": led.dir_flips,
            "dir_flips_offline": s["dir_flips_offline"],
            "dir_flips_online": s["dir_flips_online"],
            "rounds_after_coalescing": s["rounds_after_coalescing"],
            "raw_messages": s["raw_messages"],
            "seed_stream_segs": led.seed_stream_segs,
            "seed_stream_labels": led.seed_stream_labels,
            "delta_batches": led.delta_batches,
            # LAN model re-priced with the *measured* post-coalescing
            # round structure (the oracle's own time_s charges one
            # latency per metered message, i.e. pre-coalescing)
            "lan_model_offline_s_coalesced": round(led.offline.time_s(
                max_rounds=max(led.proto_frames_offline, 1)), 6),
            "lan_model_online_s_coalesced": round(led.online.time_s(
                max_rounds=max(led.proto_frames_online, 1)), 6),
            "ledger_matches_oracle": True,
        }
    finally:
        cleanup()


def _pipelined(model, cfg, x, y_ref):
    """Dedicated offline pair + online pair: the online run completes
    while refill traffic is in flight — deterministically, by holding the
    offline pair's *response* delivery behind a gate until serving is
    done (the refill request stream has left the client by then)."""
    import threading as th_mod

    from repro.net import InProcPipe, PitNetServer
    from repro.serve import NetPrivateServeEngine, PrivateRequest

    srv = PitNetServer(model, cfg["S"], impl="ref")
    off_c, off_s = InProcPipe.make_pair()
    on_c, on_s = InProcPipe.make_pair()
    srv.serve_transport(off_s, timeout=600, name="pit-eval-offline")
    srv.serve_transport(on_s, timeout=600, name="pit-eval-online")
    eng = NetPrivateServeEngine(off_c, on_c, pool_target=2, seed=7,
                                impl="ref", timeout=600)
    eng.preprocess(1)  # one bundle in the pool before the wave

    gate = th_mod.Event()
    off_c.recv_gate = gate  # offline responses held until serving is done
    t0 = time.perf_counter()
    refill = eng.refill_async(1)  # streams on the offline pair
    req = PrivateRequest(x=x)
    eng.serve([req])  # consumes the pooled bundle on the online pair
    t_serve = time.perf_counter() - t0
    online_during_refill = refill.is_alive()
    gate.set()
    refill.join(timeout=600)
    t_refill = time.perf_counter() - t0
    assert np.array_equal(req.result, y_ref), \
        "pipelined: output diverged from the in-process session"
    assert eng.pool_size() == 1, "refill did not land in the pool"
    assert online_during_refill, \
        "online serve did not overlap the in-flight refill"
    eng.close()
    return {
        "refill_s": round(t_refill, 3),
        "serve_s": round(t_serve, 3),
        "online_completed_while_refill_in_flight": bool(
            online_during_refill),
    }


def _gateway(model, cfg, x, y_ref, n_clients=3):
    """Multi-client gateway point: N concurrent TCP sessions behind one
    accept loop, every output bit-identical, one garbled slab per
    distinct netlist shared across all of them."""
    import threading as th_mod

    from repro.net import TcpListener
    from repro.serve import PitGateway, gateway_client

    gw = PitGateway(model, cfg["S"], impl="ref", max_sessions=n_clients,
                    pool_cap=4)
    lst = TcpListener()
    loop = gw.serve_listener(lst, accept_timeout=0.2, timeout=600)
    outs = [None] * n_clients
    t0 = time.perf_counter()

    def client(i):
        eng = gateway_client("127.0.0.1", lst.port, seed=100 + i,
                             impl="ref", timeout=600)
        try:
            eng.preprocess(1)
            outs[i] = eng.run(x)
        finally:
            eng.close()

    threads = [th_mod.Thread(target=client, args=(i,))
               for i in range(n_clients)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=600)
    elapsed = time.perf_counter() - t0
    for i, y in enumerate(outs):
        assert y is not None and np.array_equal(y, y_ref), \
            f"gateway: session {i} diverged from the in-process session"
    st = gw.stats()
    cache = st["garbling_cache"]
    assert cache["slabs"] == cache["distinct_netlists"], \
        "gateway: more than one garbled slab per distinct netlist"
    loop.stop()
    gw.close()
    lst.close()
    return {
        "clients": n_clients,
        "sessions_served": st["sessions_admitted"],
        "sessions_shed": st["sessions_shed"],
        "bundles_consumed": st["bundles_consumed"],
        "aggregate_bundles_per_s": round(st["bundles_consumed"]
                                         / max(elapsed, 1e-9), 3),
        "elapsed_s": round(elapsed, 3),
        "shared_cache_slabs": cache["slabs"],
        "shared_cache_hits": cache["hits"],
        "shared_cache_misses": cache["misses"],
    }


def _ot_bytes(oracle):
    """Total OT traffic (extension batches + one-time base exchange)."""
    return sum(v for phase in ("offline_by_tag", "online_by_tag")
               for t, v in oracle[phase].items()
               if t.startswith("ot:") or t == "ot-base")


def run(cfg, write=print):
    model = _model(cfg)
    rng = np.random.default_rng(1)
    x = rng.normal(0, 1, (cfg["S"], cfg["d"]))
    y_v1, oracle_v1 = _oracle(model, cfg, x, wire_version=1)
    y_ref, oracle = _oracle(model, cfg, x, wire_version=2)
    assert np.array_equal(y_v1, y_ref), \
        "wire-version knob changed the in-process output"

    points = []
    for kind in ("inproc", "tcp"):
        pt = _point(model, cfg, kind, x, y_ref, oracle)
        points.append(pt)
        write(f"net[{kind}],{pt['online_s'] * 1e6:.0f},"
              f"v{pt['wire_version']} "
              f"offline {pt['offline_bytes'] / 1e6:.2f}MB/"
              f"{pt['offline_s']}s online {pt['online_bytes'] / 1e6:.2f}MB/"
              f"{pt['online_s']}s rounds {pt['rounds_after_coalescing']}"
              f"(raw {pt['raw_messages']}) "
              f"overhead {pt['overhead_pct_of_proto']}% ledger==oracle")

    # v1 → v2 wire comparison (byte totals from the two oracles, round
    # structure from the measured inproc point)
    inp = points[0]
    v1_ot, v2_ot = _ot_bytes(oracle_v1), _ot_bytes(oracle)
    comparison = {
        "v1_offline_bytes": oracle_v1["offline_bytes"],
        "v2_offline_bytes": oracle["offline_bytes"],
        "offline_bytes_reduction_x": round(
            oracle_v1["offline_bytes"] / max(oracle["offline_bytes"], 1), 3),
        "v1_lan_model_offline_s": round(
            oracle_v1["lan_model_offline_s"], 6),
        "v2_lan_model_offline_s_coalesced":
            inp["lan_model_offline_s_coalesced"],
        "lan_model_offline_speedup_x": round(
            oracle_v1["lan_model_offline_s"]
            / max(inp["lan_model_offline_s_coalesced"], 1e-12), 3),
        "v1_ot_bytes": v1_ot,
        "v2_ot_bytes": v2_ot,
        "ot_bytes_ratio_v2_over_v1": round(v2_ot / max(v1_ot, 1), 3),
    }
    write(f"net[v2-vs-v1],0,offline "
          f"{comparison['offline_bytes_reduction_x']}x fewer bytes, "
          f"LAN-model offline {comparison['lan_model_offline_speedup_x']}x "
          f"faster, IKNP-OT/sim-OT bytes "
          f"{comparison['ot_bytes_ratio_v2_over_v1']}x")

    pipe = _pipelined(model, cfg, x, y_ref)
    write(f"net[pipelined],{pipe['serve_s'] * 1e6:.0f},"
          f"online-during-refill="
          f"{pipe['online_completed_while_refill_in_flight']}")

    # gateway fan-out: always the reduced config — 3 concurrent
    # full-size clients would dominate the bench wall-clock without
    # measuring anything new
    gmodel = _model(GATEWAY_CFG)
    grng = np.random.default_rng(1)
    gx = grng.normal(0, 1, (GATEWAY_CFG["S"], GATEWAY_CFG["d"]))
    gy, _ = _oracle(gmodel, GATEWAY_CFG, gx, wire_version=2)
    gw = _gateway(gmodel, GATEWAY_CFG, gx, gy)
    gw["model"] = (f"reduced (d={GATEWAY_CFG['d']}, S={GATEWAY_CFG['S']}) "
                   f"for bench wall-clock")
    write(f"net[gateway],{gw['elapsed_s'] * 1e6:.0f},"
          f"{gw['sessions_served']} sessions "
          f"{gw['aggregate_bundles_per_s']} bundles/s "
          f"cache {gw['shared_cache_slabs']} slabs/"
          f"{gw['shared_cache_hits']} hits")
    return {"config": cfg, "oracle": oracle, "oracle_v1": oracle_v1,
            "wire_comparison": comparison, "points": points,
            "pipelined": pipe, "gateway": gw}


def _tracer_overhead():
    """Cost of the ``repro.obs`` instrumentation on the smoke point.

    Two numbers matter:

    * ``traced_overhead_pct`` — wall-clock of one traced in-process
      smoke-oracle run vs an untraced one (machine-relative,
      informational: single runs, so noise dominates small deltas).
    * ``tracing_off_overhead_pct`` — the gated number: (span+instant
      call sites hit during the smoke run) x (measured per-call cost of
      a disabled ``obs.span()``/``close()`` pair) as a fraction of the
      untraced wall-clock. This is deterministic up to the microbench
      and is what ``--check`` holds below 1%.
    """
    from repro import obs

    model = _model(SMOKE)
    rng = np.random.default_rng(1)
    x = rng.normal(0, 1, (SMOKE["S"], SMOKE["d"]))
    _oracle(model, SMOKE, x, wire_version=2)  # warm JIT / HE caches

    prev = obs.install(obs.NULL_TRACER)
    try:
        t0 = time.perf_counter()
        _oracle(model, SMOKE, x, wire_version=2)
        untraced_s = time.perf_counter() - t0

        tr = obs.Tracer()
        obs.install(tr)
        t0 = time.perf_counter()
        _oracle(model, SMOKE, x, wire_version=2)
        traced_s = time.perf_counter() - t0
        events = len(tr.finished_spans()) + len(tr.finished_instants())

        obs.install(obs.NULL_TRACER)
        n = 100_000
        t0 = time.perf_counter_ns()
        for _ in range(n):
            obs.span("x").close()
        null_span_ns = (time.perf_counter_ns() - t0) / n
    finally:
        obs.install(prev)

    off_pct = 100.0 * events * null_span_ns * 1e-9 / max(untraced_s, 1e-9)
    return {
        "smoke_untraced_s": round(untraced_s, 4),
        "smoke_traced_s": round(traced_s, 4),
        "traced_overhead_pct": round(
            100.0 * (traced_s - untraced_s) / max(untraced_s, 1e-9), 2),
        "trace_events": events,
        "null_span_ns": round(null_span_ns, 1),
        "tracing_off_overhead_pct": round(off_pct, 4),
    }


def _resilience_overhead():
    """Cost of the fault-injection wrapper with faults DISABLED.

    The resilience stack is meant to stay on in production, so an
    empty-schedule :class:`FaultyTransport` must be near-free: its hot
    path adds one locked counter increment + dict miss per transport op.
    Two numbers:

    * ``wrapped_overhead_pct`` — wall-clock of one wrapped smoke run vs
      a plain one (machine-relative, informational: single runs, noise
      dominates small deltas).
    * ``faults_off_overhead_pct`` — the gated number: (transport ops in
      the smoke run) x (measured per-op cost of the wrapper's no-fault
      bookkeeping) as a fraction of the plain wall-clock. Deterministic
      up to the microbench; ``--check`` holds it below 2%.
    """
    from repro.net import (FaultyTransport, GarblerEndpoint, InProcPipe,
                           PitNetServer)

    model = _model(SMOKE)
    rng = np.random.default_rng(1)
    x = rng.normal(0, 1, (SMOKE["S"], SMOKE["d"]))
    _oracle(model, SMOKE, x, wire_version=2)  # warm JIT / HE caches

    def run_once(wrap):
        srv = PitNetServer(model, SMOKE["S"], impl="ref")
        a, b = InProcPipe.make_pair()
        srv.serve_transport(b, timeout=600)
        t = FaultyTransport(a) if wrap else a
        cli = GarblerEndpoint(t, seed=7, impl="ref", timeout=600)
        t0 = time.perf_counter()
        cli.preprocess(1)
        y = cli.run(x)
        elapsed = time.perf_counter() - t0
        ops = t.op if wrap else (a.frames_sent + a.frames_recv)
        cli.close()
        return y, elapsed, ops

    y_plain, plain_s, _ = run_once(wrap=False)
    y_wrapped, wrapped_s, ops = run_once(wrap=True)
    assert np.array_equal(y_plain, y_wrapped), \
        "an empty fault schedule changed the protocol output"

    ft = FaultyTransport(InProcPipe.make_pair()[0])
    n = 100_000
    t0 = time.perf_counter_ns()
    for _ in range(n):
        ft._next_fault()  # the whole no-fault hot path: counter + miss
    null_op_ns = (time.perf_counter_ns() - t0) / n

    off_pct = 100.0 * ops * null_op_ns * 1e-9 / max(plain_s, 1e-9)
    return {
        "smoke_plain_s": round(plain_s, 4),
        "smoke_wrapped_s": round(wrapped_s, 4),
        "wrapped_overhead_pct": round(
            100.0 * (wrapped_s - plain_s) / max(plain_s, 1e-9), 2),
        "transport_ops": ops,
        "null_op_ns": round(null_op_ns, 1),
        "faults_off_overhead_pct": round(off_pct, 4),
    }


def _smoke_oracle():
    """Byte/round counts of the smoke config at both wire versions —
    the deterministic reference ``check()`` ratchets against."""
    model = _model(SMOKE)
    rng = np.random.default_rng(1)
    x = rng.normal(0, 1, (SMOKE["S"], SMOKE["d"]))
    _, o1 = _oracle(model, SMOKE, x, wire_version=1)
    _, o2 = _oracle(model, SMOKE, x, wire_version=2)
    keep = ("offline_bytes", "online_bytes", "offline_msgs", "online_msgs")
    return {"v1": {k: o1[k] for k in keep}, "v2": {k: o2[k] for k in keep}}


def full():
    result = {"bench": "net", **run(FULL, write=lambda m: print(m, flush=True))}
    result["smoke_oracle"] = _smoke_oracle()
    result["tracer_overhead"] = _tracer_overhead()
    result["resilience_overhead"] = _resilience_overhead()
    out = Path(__file__).resolve().parents[1] / "BENCH_net.json"
    out.write_text(json.dumps(result, indent=2) + "\n")
    print(f"# wrote {out}", flush=True)
    o, cmp_, pts = result["oracle"], result["wire_comparison"], \
        result["points"]
    print(f"# v2 oracle msgs: {o['offline_msgs']} offline / "
          f"{o['online_msgs']} online; offline bytes "
          f"{cmp_['v1_offline_bytes'] / 1e6:.1f}MB → "
          f"{cmp_['v2_offline_bytes'] / 1e6:.1f}MB "
          f"({cmp_['offline_bytes_reduction_x']}x); LAN-model offline "
          f"{cmp_['v1_lan_model_offline_s']:.3f}s → "
          f"{cmp_['v2_lan_model_offline_s_coalesced']:.3f}s "
          f"({cmp_['lan_model_offline_speedup_x']}x); measured online: "
          + ", ".join(f"{p['transport']}={p['online_s']}s" for p in pts))
    return result


def check() -> None:
    """Net wire ratchet (CI, via ``benchmarks/run.py --check``):
    re-derive the smoke-config oracle byte/round counts and fail on a
    >20% byte regression — or any message-count growth — against the
    committed ``BENCH_net.json``."""
    path = Path(__file__).resolve().parents[1] / "BENCH_net.json"
    ref = json.loads(path.read_text()).get("smoke_oracle")
    assert ref, f"{path} has no smoke_oracle section — rerun the full bench"
    got = _smoke_oracle()
    for ver in ("v1", "v2"):
        for key in ("offline_bytes", "online_bytes"):
            g, w = got[ver][key], ref[ver][key]
            assert g <= w * 1.2, \
                f"net ratchet: {ver} {key} regressed {w} → {g} (>20%)"
        for key in ("offline_msgs", "online_msgs"):
            g, w = got[ver][key], ref[ver][key]
            assert g <= w, \
                f"net ratchet: {ver} {key} grew {w} → {g}"
    assert got["v2"]["offline_bytes"] < got["v1"]["offline_bytes"], \
        "net ratchet: v2 no longer compresses the offline phase"
    committed = json.loads(path.read_text())
    assert "tracer_overhead" in committed, \
        f"{path} has no tracer_overhead section — rerun the full bench"
    assert "resilience_overhead" in committed, \
        f"{path} has no resilience_overhead section — rerun the full bench"
    ov = _tracer_overhead()
    assert ov["tracing_off_overhead_pct"] < 1.0, \
        (f"obs instrumentation costs "
         f"{ov['tracing_off_overhead_pct']:.3f}% of the smoke point with "
         f"tracing OFF ({ov['trace_events']} call sites x "
         f"{ov['null_span_ns']:.0f}ns null span) — must stay <1%")
    rov = _resilience_overhead()
    assert rov["faults_off_overhead_pct"] < 2.0, \
        (f"fault-injection wrapper costs "
         f"{rov['faults_off_overhead_pct']:.3f}% of the smoke point with "
         f"faults DISABLED ({rov['transport_ops']} transport ops x "
         f"{rov['null_op_ns']:.0f}ns null op) — must stay <2%")
    print(f"net check OK: smoke oracle v1 "
          f"{got['v1']['offline_bytes']}B / v2 "
          f"{got['v2']['offline_bytes']}B offline within ratchet; "
          f"tracing-off overhead {ov['tracing_off_overhead_pct']:.4f}% "
          f"(<1%); faults-off overhead "
          f"{rov['faults_off_overhead_pct']:.4f}% (<2%)", flush=True)


def main() -> None:
    """Smoke entry for benchmarks/run.py and CI: tiny config, both
    transports + the pipelined overlap check, parity/ledger asserted."""
    res = run(SMOKE)
    assert all(p["ledger_matches_oracle"] for p in res["points"])
    assert all(p["wire_version"] == 2 for p in res["points"])
    assert all(p["rounds_after_coalescing"] < p["raw_messages"]
               for p in res["points"])
    assert res["pipelined"]["online_completed_while_refill_in_flight"]
    assert res["gateway"]["sessions_served"] == res["gateway"]["clients"]


if __name__ == "__main__":
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    if "--check" in sys.argv:
        check()
    elif "--smoke" in sys.argv:
        main()
    else:
        full()
