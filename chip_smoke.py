#!/usr/bin/env python3
"""Smoke run of the private-serving path on one TPU chip.

Serves one BERT-base-width encoder layer privately, through the front door
a deployment uses: a ``PitGateway`` on a loopback TCP listener and one
pipelined ``gateway_client``, both as threads of this one process (the
process that holds the chip). Widths come from
``repro.configs.bert_base_pit`` (d_model=768, 12 heads, d_ff=3072); the
weights are random from ``--seed``; the privacy config is the paper's
protocol (40-bit plaintext prime, 7 fractional bits, LayerNorm offload).
Depth is cut to one layer and the sequence bucket to S=8. The client
preprocesses two bundles and serves two requests. Each revealed output must
be within 0.25 of the float forward pass of the fixed-point model the
protocol computes: the input, the linear weights and β rounded to 7
fractional bits as the protocol rounds them (``max|priv-fixed|``). The
distance to the unrounded float model (``max|priv-float|``) is printed
too, with the part of it that the 7-bit weights alone cause
(``max|fixed-float|``): at this width that part is larger than 0.25.

A first phase garbles and evaluates two real netlists with the Pallas
executor and with the host numpy oracle and requires bit-identical garbled
tables, ``output_perm`` and output labels: GELU at I=4096 (throughput
regime) and the 8-element softmax row at I=1 (latency regime, prefetch on).

It exits non-zero, printing no result, when JAX finds no TPU or when any
phase fails. Its last stdout line is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Run from the repository root: ``python chip_smoke.py [--seed N]``.
"""

from __future__ import annotations

import argparse
import copy
import dataclasses
import json
import re
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import obs  # noqa: E402
from repro.config import PrivacyConfig  # noqa: E402
from repro.configs.bert_base_pit import CONFIG  # noqa: E402
from repro.core import garble as G  # noqa: E402
from repro.core.engine import PrivateTransformer, random_weights  # noqa: E402
from repro.core.gc_exec import get_executor  # noqa: E402
from repro.core.plan import GC_KINDS  # noqa: E402
from repro.core.protocol import PiTProtocol  # noqa: E402
from repro.core.session import gc_net_for  # noqa: E402
from repro.kernels.dispatch import resolve_impl  # noqa: E402
from repro.net import TcpListener  # noqa: E402
from repro.serve import PitGateway, gateway_client  # noqa: E402
from repro.utils.compile_cache import use_compile_cache  # noqa: E402

#: the bound the session tests hold the private forward pass to
ERR_BOUND = 0.25
LAYERS = 1
SEQ = 8
BUNDLES = 2
REQUESTS = 2
#: wire receive deadline: a full-width bundle streams ~2.4 GB of tables
WIRE_TIMEOUT_S = 1200.0
#: the paper's protocol: 40-bit plaintext prime, 7 fractional bits
PCFG = PrivacyConfig(he_t_bits=40, frac_bits=7, layernorm_offload=True)
#: (netlist, instances): the throughput and the latency regime
PARITY_CASES = (("gelu", 4096), ("softmax8", 1))
PARITY_IMPL = "pallas"
#: the host numpy walk the parity phase holds the Pallas executor to
ORACLE_IMPL = "ref"


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or loading a
    compiled program from the persistent cache), summed over all threads,
    plus persistent-cache hit and miss counts."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def reset(self) -> None:
        with self._lock:
            self.seconds = 0.0
            self.cache_hits = self.cache_misses = 0

    def _duration(self, event, duration, **_):
        if event in self.EVENTS:
            with self._lock:
                self.seconds += duration

    def _event(self, event, **_):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1


def span_seconds(name: str) -> float:
    return sum(s.elapsed_s for s in obs.current().finished_spans()
               if s.name == name)


def fixed_point_reference(model):
    """``(reference model, input rounding)``: the float forward pass over
    the weights the protocol holds — linear weights (the query weights
    after their 1/sqrt(head_dim) scale) and β rounded to ``frac`` bits by
    the protocol's own quantizer; γ enters as γ/n at a finer scale and
    stays float."""
    p = model.p
    step = float(1 << p.frac)
    rnd = lambda a: p.quantize_weight(a)[0] / step  # noqa: E731
    ref = copy.copy(model)
    ref.weights = [dataclasses.replace(
        w, wq=rnd(w.wq * model.scale_q) / model.scale_q, wk=rnd(w.wk),
        wv=rnd(w.wv), wo=rnd(w.wo), w1=rnd(w.w1), w2=rnd(w.w2),
        ln1_b=rnd(w.ln1_b), ln2_b=rnd(w.ln2_b)) for w in model.weights]
    return ref, (lambda x: np.round(x * step) / step)


def serve_phase(seed: int, clock: CompileClock) -> None:
    """Full-width private serving through PitGateway over loopback TCP."""
    d, heads, d_ff = CONFIG.d_model, CONFIG.num_heads, CONFIG.d_ff
    log(f"model: {CONFIG.name} d_model={d} heads={heads} d_ff={d_ff} "
        f"(published widths); cuts: layers={LAYERS} (of "
        f"{CONFIG.num_layers}), S={SEQ}; random weights, seed={seed}")
    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    weights = random_weights(rng, d, d_ff, LAYERS)
    model = PrivateTransformer(PCFG, d, heads, d_ff, weights, seed=seed)
    gw = PitGateway(model, SEQ, seed=seed + 1, max_sessions=1,
                    pool_cap=BUNDLES)
    lst = TcpListener()
    loop = gw.serve_listener(lst, timeout=WIRE_TIMEOUT_S)
    eng = None
    try:
        eng = gateway_client("127.0.0.1", lst.port, pool_target=BUNDLES,
                             seed=seed + 2, timeout=WIRE_TIMEOUT_S)
        t_up = time.perf_counter() - t0
        p = gw.shared.protocol
        log(f"gateway up over TCP port {lst.port}: impl={p.impl} "
            f"t={p.t} gc_word={p.k}b ({t_up:.3f}s: plan trace, HE keys "
            f"for model, server and client, handshake)")

        prep_s = []
        for i in range(BUNDLES):
            c0 = clock.seconds
            t = time.perf_counter()
            eng.preprocess(1)  # returns after prep-done: host arrays
            prep_s.append(time.perf_counter() - t)
            log(f"preprocess bundle {i + 1}: {prep_s[-1]:.3f}s wall "
                f"(of which XLA/Mosaic compile "
                f"{clock.seconds - c0:.3f}s)")
        cache = gw.stats()["garbling_cache"]
        ands = sum(net.and_count * per
                   for net, per in _gc_work(gw.shared))
        log(f"AND gates per bundle: {ands} ({ands * 32 / 1e9:.2f} GB of "
            f"garbled tables); garbling cache {cache['by_key']}")

        ref, rnd_input = fixed_point_reference(model)
        worst = 0.0
        for i in range(REQUESTS):
            x = rng.normal(0, 1, (SEQ, d))
            c0 = clock.seconds
            t = time.perf_counter()
            y = eng.run(x)  # revealed host array: the device work is done
            dt = time.perf_counter() - t
            y_float = model.forward_float(x)
            y_fixed = ref.forward_float(rnd_input(x))
            err = float(np.abs(y - y_fixed).max())
            worst = max(worst, err)
            log(f"request {i + 1}: online {dt:.3f}s wall (of which "
                f"XLA/Mosaic compile {clock.seconds - c0:.3f}s), "
                f"max|priv-fixed|={err:.6f}, "
                f"max|priv-float|={np.abs(y - y_float).max():.6f}, "
                f"max|fixed-float|={np.abs(y_fixed - y_float).max():.6f}, "
                f"finite={bool(np.isfinite(y).all())}, "
                f"shape={tuple(y.shape)}")
            if not (np.isfinite(y).all() and y.shape == (SEQ, d)):
                raise RuntimeError(f"request {i + 1}: bad output")
        log(f"set-up: netlist generation {span_seconds('netlist.build'):.3f}s "
            f"(both parties)")
        log(f"set-up: plan compile {span_seconds('netlist.plan'):.3f}s "
            f"(both parties)")
        for s in obs.current().finished_spans():
            if s.name in ("netlist.build", "netlist.plan") \
                    and s.elapsed_s > 1.0:
                log(f"  {s.name} {s.attrs}: {s.elapsed_s:.3f}s")
        log(f"set-up: XLA/Mosaic compile {clock.seconds:.3f}s "
            f"(persistent cache: {clock.cache_hits} hits, "
            f"{clock.cache_misses} misses)")
        st = gw.stats()
        log(f"gateway: {st['bundles_prepped']} prepped, "
            f"{st['bundles_consumed']} consumed, "
            f"{st['bundles_outstanding']} outstanding")
        if st["bundles_consumed"] != REQUESTS:
            raise RuntimeError("gateway did not consume every bundle")
        if worst >= ERR_BOUND:
            raise RuntimeError(
                f"max|priv-fixed|={worst:.6f} >= {ERR_BOUND}")
    finally:
        if eng is not None:
            eng.close()
        loop.stop()
        lst.close()
        gw.close()
        gw.join(timeout=60)


def _gc_work(shared):
    """(netlist, instances per request) for every GC op of the plan."""
    return [(gc_net_for(shared.protocol, op), shared.plan.gc_instances(op))
            for op in shared.plan.ops if op.kind in GC_KINDS]


def parity_phase(seed: int) -> None:
    """Pallas executor vs host numpy oracle on two real netlists."""
    p = PiTProtocol(PCFG, seed=seed)
    nets = {"gelu": lambda: p.activation_net("gelu", 2 * p.frac),
            "softmax8": lambda: p.softmax_net(8, 2 * p.frac)}
    rng = np.random.default_rng(seed)
    for name, I in PARITY_CASES:
        net = nets[name]()
        key = jax.random.PRNGKey(seed)
        t = time.perf_counter()
        dev = G.garble(net, key, I, impl=PARITY_IMPL)
        jax.block_until_ready(dev.tables)
        t_dev = time.perf_counter() - t
        ref = G.garble(net, key, I, impl=ORACLE_IMPL)
        gb = rng.integers(0, 2, (I, len(net.garbler_inputs)))
        eb = rng.integers(0, 2, (I, len(net.evaluator_inputs)))
        outs = []
        for gc, impl in ((dev, PARITY_IMPL), (ref, ORACLE_IMPL)):
            cw, c_lab = G.const_wires_labels(gc)
            ids = np.concatenate([net.garbler_inputs, net.evaluator_inputs,
                                  cw]).astype(np.int64)
            labels = jnp.concatenate([
                G.encode_inputs(gc, net.garbler_inputs, gb),
                G.encode_inputs(gc, net.evaluator_inputs, eb), c_lab], 1)
            t = time.perf_counter()
            out = G.evaluate(net, gc.tables, (ids, labels), impl=impl)
            outs.append(np.asarray(jax.block_until_ready(out)))
            if impl == PARITY_IMPL:
                t_eval = time.perf_counter() - t
        same = {
            "tables": np.array_equal(np.asarray(dev.tables),
                                     np.asarray(ref.tables)),
            "output_perm": np.array_equal(np.asarray(dev.output_perm),
                                          np.asarray(ref.output_perm)),
            "output_labels": np.array_equal(outs[0], outs[1]),
        }
        decoded = G.decode_outputs(dev, outs[0])
        plain = np.array_equal(decoded, net.eval_plain(gb, eb))
        exe_g = get_executor(net, I, PARITY_IMPL, garbling=True)
        exe_e = get_executor(net, I, PARITY_IMPL)
        walks = _walk_facts(exe_g, exe_e, net, I)
        log(f"parity {name} I={I} ands={net.and_count}: bit-identical "
            f"{same}, decodes to plaintext {plain}; pallas garble "
            f"{t_dev:.3f}s, evaluate {t_eval:.3f}s (first call, compile "
            f"included); prefetch={exe_e.prefetch}; {walks}")
        if not (all(same.values()) and plain):
            raise RuntimeError(f"parity {name}: pallas differs from ref")


def _walk_facts(exe_g, exe_e, net, I) -> str:
    """Is the Pallas kernel in the compiled garble and eval walks, and did
    any 64-bit integer op from the global x64 switch reach them?"""
    u = lambda *s: jax.ShapeDtypeStruct(s, jnp.uint32)
    labels = u(I, exe_e.n_src, 4)
    texts = {
        "garble": exe_g._garble.lower(labels, u(I, 4)).compile().as_text(),
        "eval": exe_e._eval.lower(
            labels, u(I, max(net.and_count, 1), 2, 4)).compile().as_text(),
    }
    facts = []
    for lane, text in texts.items():
        wide = sorted(set(re.findall(r"\b[su]64\[", text)))
        facts.append(f"{lane} walk: tpu_custom_call="
                     f"{'tpu_custom_call' in text} 64-bit ops={wide or 'none'}")
        if "tpu_custom_call" not in text:
            raise RuntimeError(f"{lane} walk has no Pallas kernel")
    return "; ".join(facts)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cache_dir = use_compile_cache()
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {device}); this run "
              f"needs a TPU and does not fall back to the CPU",
              file=sys.stderr)
        return 2
    impl = resolve_impl("auto")
    log(f"device: platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}; resolved impl={impl}; "
        f"compile cache={cache_dir}")
    if impl != "pallas":
        print(f"chip_smoke: auto resolved to {impl!r}, not 'pallas'",
              file=sys.stderr)
        return 2

    obs.enable()  # host spans: netlist generation and plan compile times
    clock = CompileClock()
    t0 = time.perf_counter()
    parity_phase(args.seed)
    log(f"parity phase: {time.perf_counter() - t0:.3f}s")
    t0 = time.perf_counter()
    obs.current().clear()  # set-up lines count the serving phase alone
    clock.reset()
    serve_phase(args.seed, clock)
    log(f"serving phase: {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
