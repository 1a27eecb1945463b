"""GC online-path microbench: gates/s of the device-resident executor
vs the per-level numpy loop, on protocol softmax-row netlists.

This is the repo's perf gate for the hottest online code in hybrid PiT —
:func:`repro.core.garble.evaluate` — the path every ``session.run`` /
``PrivateServeEngine.serve`` request takes. Two implementations of the
same bit-exact walk are raced:

  ref   per-level numpy loop (gather -> XOR/INV/Half-Gate batches ->
        scatter, one Python round trip per topological level)
  auto  device-resident executor (:mod:`repro.core.gc_exec`): the whole
        netlist compiled into ONE jitted scan through the fused level
        kernel

Two softmax-row configurations are swept:

* ``softmax8 @ 40-bit shares`` — the production share modulus
  (``bench_protocol``'s config), from the single-request latency point
  (I=1, where the executor's latency-regime plan applies) up to
  preprocessing-scale batches. The recorded headline (>= 5x gates/s
  over the numpy loop) is this config's online-latency point — the
  metric APINT optimizes — where the numpy loop is pure per-level
  dispatch overhead and the compiled walk replaces ~2100 Python round
  trips with one launch; large batches are bandwidth-bound on both
  sides and win ~2-3x.
* ``softmax2 @ 12-bit shares`` — a quantized row (aggressive word-width
  reduction is APINT's own direction, XFBQ/Fig. 5), recorded as the
  secondary config.

``python benchmarks/bench_gc_eval.py`` runs both sweeps and writes
``BENCH_gc_eval.json`` at the repo root (keeping the previously
committed speedups per point as ``prev`` for comparison); ``--smoke``
(CI and ``benchmarks/run.py``) runs the quantized row at the I=4 online
point plus a preprocessing-scale I=64 garble-parity point and asserts
parity + sane speedups on both paths. :func:`check` (``run.py
--check``) re-measures a small subset and fails on a >20% speedup
regression against the committed JSON.

Every point embeds the executor plan's :meth:`LevelPlan.stats` so the
liveness-compaction and packed-table wins are visible per netlist.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np

#: (row_len, frac_bits, he_t_bits, he_poly_n, he_num_primes)
PROD = {"label": "softmax8 @ 40-bit shares",
        "row_len": 8, "frac": 6, "t_bits": 40, "poly_n": 256, "primes": 3}
QUANT = {"label": "softmax2 @ 12-bit shares (quantized row)",
         "row_len": 2, "frac": 4, "t_bits": 12, "poly_n": 64, "primes": 2}


def _net(cfg):
    import jax

    jax.config.update("jax_enable_x64", True)
    from repro.config import PrivacyConfig
    from repro.core.protocol import PiTProtocol

    pcfg = PrivacyConfig(he_poly_n=cfg["poly_n"],
                         he_num_primes=cfg["primes"],
                         he_t_bits=cfg["t_bits"], frac_bits=cfg["frac"],
                         layernorm_offload=True)
    return PiTProtocol(pcfg, seed=0).softmax_net(cfg["row_len"],
                                                 cfg["frac"])


def _active_labels(net, gc, rng):
    from repro.core import garble as G

    I = gc.num_instances
    bits = rng.integers(0, 2, (I, len(net.garbler_inputs)
                               + len(net.evaluator_inputs)))
    wire_ids = np.concatenate([
        np.asarray(net.garbler_inputs, np.int64),
        np.asarray(net.evaluator_inputs, np.int64)])
    labels = np.asarray(G.encode_inputs(gc, wire_ids, bits))
    cw, cl = G.const_wires_labels(gc)
    return (np.concatenate([wire_ids, cw]),
            np.concatenate([labels, np.asarray(cl)], axis=1))


def _block(x):
    import jax

    jax.tree_util.tree_map(lambda a: a.block_until_ready(), x)
    return x


def _median(times):
    return sorted(times)[len(times) // 2]


def _point(net, instances: int, device_impl: str, reps: int, rounds: int):
    """One (netlist, I) measurement: eval + garble, ref vs device.

    Median of ``rounds`` timing rounds of ``reps`` calls each — the box
    this runs on is noisy and a single average is not reproducible.
    """
    import jax

    from repro.core import garble as G

    key = jax.random.PRNGKey(0)
    rng = np.random.default_rng(0)

    gc = G.garble(net, key, instances, impl="ref")
    active = _active_labels(net, gc, rng)
    out_ref = G.evaluate(net, gc.tables, active, impl="ref")
    out_dev = _block(G.evaluate(net, gc.tables, active, impl=device_impl))
    assert np.array_equal(np.asarray(out_ref), np.asarray(out_dev)), \
        "device executor diverged from the numpy oracle"

    t_ref, t_dev, t_gref, t_gdev = [], [], [], []
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(reps):
            G.evaluate(net, gc.tables, active, impl="ref")
        t_ref.append((time.perf_counter() - t0) / reps)
        t0 = time.perf_counter()
        for _ in range(reps):
            _block(G.evaluate(net, gc.tables, active, impl=device_impl))
        t_dev.append((time.perf_counter() - t0) / reps)
    gdev = G.garble(net, key, instances, impl=device_impl)
    _block(gdev.tables)
    assert np.array_equal(np.asarray(gc.tables), np.asarray(gdev.tables))
    for _ in range(max(rounds // 2, 1)):
        t0 = time.perf_counter()
        G.garble(net, key, instances, impl="ref")
        t_gref.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        _block(G.garble(net, key, instances, impl=device_impl).tables)
        t_gdev.append(time.perf_counter() - t0)

    tr, td = _median(t_ref), _median(t_dev)
    tgr, tgd = _median(t_gref), _median(t_gdev)
    gps = net.num_gates * instances
    return {
        "instances": instances,
        "eval": {
            "ref_ms": round(tr * 1e3, 1),
            "device_ms": round(td * 1e3, 1),
            "ref_mgates_per_s": round(gps / tr / 1e6, 2),
            "device_mgates_per_s": round(gps / td / 1e6, 2),
            "speedup": round(tr / td, 2),
        },
        "garble": {
            "ref_ms": round(tgr * 1e3, 1),
            "device_ms": round(tgd * 1e3, 1),
            "speedup": round(tgr / tgd, 2),
        },
    }


def _prev_points(label):
    """Committed speedups per (label, instances) from BENCH_gc_eval.json."""
    path = Path(__file__).resolve().parents[1] / "BENCH_gc_eval.json"
    if not path.exists():
        return {}
    try:
        committed = json.loads(path.read_text())
    except json.JSONDecodeError:
        return {}
    out = {}
    for c in committed.get("configs", []):
        if c.get("label") != label:
            continue
        for p in c.get("points", []):
            out[p["instances"]] = {
                "eval_speedup": p["eval"]["speedup"],
                "garble_speedup": p["garble"]["speedup"],
            }
    return out


def run_config(cfg, instance_counts, rounds=4, write=print):
    from repro.core.netlist import compile_level_plan
    from repro.kernels.dispatch import resolve_impl

    device_impl = resolve_impl("auto")
    net = _net(cfg)
    prev = _prev_points(cfg["label"])
    points = []
    for inst in instance_counts:
        reps = 3 if inst <= 16 else 1
        r = rounds if inst <= 256 else 2
        pt = _point(net, inst, device_impl, reps, r)
        plan = compile_level_plan(net, instances=inst)
        # plan stats: store rows before/after the liveness pass, real vs
        # padded table rows — the reuse wins, per netlist and regime
        pt["plan"] = plan.stats()
        gplan = compile_level_plan(net, instances=inst, garbling=True)
        if gplan is not plan:  # AND-rich throughput: garble-width plan
            pt["plan_garble"] = gplan.stats()
        if inst in prev:
            pt["prev"] = prev[inst]  # committed trajectory, for diffing
        points.append(pt)
        e = pt["eval"]
        write(f"gc_eval[{net.name}@{cfg['t_bits']}b]_I{inst},"
              f"{e['device_ms'] * 1e3:.0f},"
              f"eval {e['device_mgates_per_s']}Mg/s vs ref "
              f"{e['ref_mgates_per_s']}Mg/s = {e['speedup']}x "
              f"garble {pt['garble']['speedup']}x")
        s = pt["plan"]
        write(f"# plan[{net.name}]_I{inst}: store {s['store_rows']} rows "
              f"(naive {s['store_rows_naive']}, "
              f"{s['store_row_reduction']}x reuse), tables "
              f"{s['table_rows_real']} real / {s['table_rows_padded']} "
              f"padded lanes")
    plan = compile_level_plan(net)
    return {
        "label": cfg["label"],
        "netlist": {"name": net.name, "t_bits": cfg["t_bits"],
                    "frac_bits": cfg["frac"], "gates": net.num_gates,
                    "and": net.and_count, "depth": plan.n_levels},
        "device_impl": device_impl,
        "plan_stats": plan.stats(),
        "points": points,
    }


def full():
    def write(msg):
        print(msg, flush=True)

    prod = run_config(PROD, (1, 16, 256, 2048), rounds=6, write=write)
    quant = run_config(QUANT, (4, 16, 256), write=write)
    lat = prod["points"][0]
    thr = prod["points"][-1]

    def _garble_at(cfgres, inst):
        for p in cfgres["points"]:
            if p["instances"] == inst:
                return p["garble"]["speedup"]
        return None

    result = {
        "bench": "gc_eval",
        "configs": [prod, quant],
        "headline": {
            "config": prod["label"],
            "instances": lat["instances"],
            "eval_speedup_vs_numpy_loop": lat["eval"]["speedup"],
            "eval_mgates_per_s": lat["eval"]["device_mgates_per_s"],
            "garble_speedup": lat["garble"]["speedup"],
            "target_speedup": 5.0,
            "meets_target": lat["eval"]["speedup"] >= 5.0,
            "throughput_instances": thr["instances"],
            "throughput_eval_speedup": thr["eval"]["speedup"],
            # the garble-path overhaul's acceptance metric: offline
            # (preprocessing) garbling at I=256 on both netlists
            "garble_speedup_at_256": {
                prod["label"]: _garble_at(prod, 256),
                quant["label"]: _garble_at(quant, 256),
            },
            "garble_target_at_256": 3.0,
        },
    }
    out = Path(__file__).resolve().parents[1] / "BENCH_gc_eval.json"
    out.write_text(json.dumps(result, indent=2) + "\n")
    print(f"# wrote {out}", flush=True)
    h = result["headline"]
    print(f"# headline ({h['config']}, online latency I="
          f"{h['instances']}): {h['eval_speedup_vs_numpy_loop']}x eval / "
          f"{h['garble_speedup']}x garble — target >= "
          f"{h['target_speedup']}x: "
          f"{'PASS' if h['meets_target'] else 'FAIL'}; throughput (I="
          f"{h['throughput_instances']}): {h['throughput_eval_speedup']}x; "
          f"garble@256: {h['garble_speedup_at_256']}")
    return result


def main() -> None:
    """Smoke entry for benchmarks/run.py and CI (no JSON).

    Two quantized-row points: the I=4 online point (parity + eval
    regression floor, as before) and a preprocessing-scale I=64 point
    that exercises the throughput-regime garble path — packed table
    emission, the liveness-compacted planar store and the split-hash
    cipher — with bit-parity against the numpy oracle asserted inside
    ``_point`` and a garble speedup floor. The I=64 garble measures
    ~3-4x here; the floors (2x eval online, 1.3x garble offline) leave
    headroom for noisy CI runners while still catching a garble path
    that has fallen back behind the numpy loop.
    """
    res = run_config(QUANT, (4, 64), rounds=2)
    speedup = res["points"][0]["eval"]["speedup"]
    assert speedup >= 2.0, \
        f"device executor regressed: {speedup}x vs numpy loop (floor 2x)"
    g64 = res["points"][1]["garble"]["speedup"]
    assert g64 >= 1.3, \
        f"garble path regressed: {g64}x vs numpy loop at I=64 (floor 1.3x)"


def check() -> None:
    """Regression gate for ``benchmarks/run.py --check``.

    Re-measures a small subset of the committed trajectory (quantized
    row, online I=4 and preprocessing I=256) and fails when a freshly
    measured speedup drops more than 20% below the committed
    ``BENCH_gc_eval.json`` value. Speedups are ratios of two runs on the
    same box, so they transfer across machines far better than absolute
    times — but not perfectly (core count shifts the jit-vs-numpy ratio),
    so a point that misses the 20% band still passes while it clears the
    absolute health floors below: the gate's job is to catch the garble
    path sliding back toward the numpy loop, not to fail unrelated PRs
    on a differently shaped runner.
    """
    # a point regressed >20% vs committed AND below these is a failure;
    # above them the path is unambiguously healthy on any runner
    floors = {"eval": 3.0, "garble": 2.0}
    path = Path(__file__).resolve().parents[1] / "BENCH_gc_eval.json"
    committed = json.loads(path.read_text())
    want = {}
    for c in committed["configs"]:
        if c["label"] != QUANT["label"]:
            continue
        for p in c["points"]:
            want[p["instances"]] = p
    insts = [i for i in (4, 256) if i in want]
    if not insts:
        raise AssertionError(
            "committed BENCH_gc_eval.json has no quantized points")
    res = run_config(QUANT, tuple(insts), rounds=3)
    failures = []
    for p in res["points"]:
        ref = want[p["instances"]]
        for path_ in ("eval", "garble"):
            got = p[path_]["speedup"]
            exp = ref[path_]["speedup"]
            bad = got < 0.8 * exp and got < floors[path_]
            status = ("REGRESSED" if bad else
                      "ok" if got >= 0.8 * exp else "ok (above floor)")
            print(f"# check {path_}@I{p['instances']}: {got}x vs "
                  f"committed {exp}x (floor {floors[path_]}x) -> "
                  f"{status}", flush=True)
            if bad:
                failures.append(
                    f"{path_}@I{p['instances']}: {got}x < 80% of "
                    f"committed {exp}x and < {floors[path_]}x floor")
    if failures:
        raise AssertionError(
            "gc_eval speedups regressed >20% vs committed "
            f"BENCH_gc_eval.json: {failures}")
    print("# check passed: speedups within 20% of committed "
          "(or above the health floors)", flush=True)


if __name__ == "__main__":
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    if "--smoke" in sys.argv:
        main()
    elif "--check" in sys.argv:
        check()
    else:
        full()
