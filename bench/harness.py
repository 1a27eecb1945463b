"""Drive one cell of ``BENCHMARK.json`` on the device this process holds.

A cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``); each metric is a reader in
``bench/metrics/<name>.py``. A configuration names its architecture's
family (``bench/families/<family>.py``), which holds its weights, plain
reference, FLOP count and the builder of the model the program serves.
Everything is found by the names that ``BENCHMARK.json`` and the
configuration give, so a cell, a metric or an architecture is added with
files and entries alone.

One run is one process. The gateway listens on a loopback TCP port and
the client connects to it, both as threads of this process. Set-up builds
the system from the seed, makes the bundles the traffic needs and warms
every shape the window uses; the window then drives the client for
``--seconds``; after it, the outputs are compared with the family's plain
reference.
"""

from __future__ import annotations

import contextlib
import functools
import importlib.util
import json
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import compile_clock
from bench import model as M
from bench import trace as T

BENCH = Path(__file__).resolve().parent
#: receive deadline on both sockets: a BERT-base bundle streams ~2.4 GB
WIRE_TIMEOUT_S = 1200.0
#: the u64 length prefix ``TcpTransport`` puts before every frame
FRAME_PREFIX_BYTES = 8
#: what a check reads when there is no finite output to compare
NO_OUTPUT = 1e30
#: index of the warm-up request's input (the window's count up from 0)
WARMUP_INPUT = 1 << 40


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the benchmark's files, found by name
# ---------------------------------------------------------------------------


@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]
    family: ModuleType


def load_benchmark(root: Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def traffic_file(root: Path, name: str) -> Path:
    return root / "bench" / "traffic" / f"{name}.json"


def metric_file(root: Path, name: str) -> Path:
    return root / "bench" / "metrics" / f"{name}.py"


def family_file(root: Path, name: str) -> Path:
    return root / "bench" / "families" / f"{name}.py"


def _load_module(path: Path, prefix: str, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        prefix + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    # registered before it runs, as an import would be, so that code which
    # looks its module up (a dataclass, pickling) finds it
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


#: every key a traffic file of each kind may hold, all of them honoured:
#: a mix that asks for anything else fails to load rather than run as
#: something it does not say
TRAFFIC_KEYS = {
    "online": {"kind", "seq_len", "trace_pool"},
    "offline": {"kind", "seq_len", "bundles_per_call", "check_bundles"},
}


def load_traffic(root: Path, name: str) -> dict:
    traffic = json.loads(traffic_file(root, name).read_text())
    kind = traffic.get("kind")
    if kind not in TRAFFIC_KEYS:
        raise ValueError(f"traffic {name!r}: kind {kind!r} is not one of "
                         f"{sorted(TRAFFIC_KEYS)}")
    keys = set(traffic)
    if keys != TRAFFIC_KEYS[kind]:
        raise ValueError(
            f"traffic {name!r}: the harness runs {kind} mixes with exactly "
            f"{sorted(TRAFFIC_KEYS[kind])}; unknown "
            f"{sorted(keys - TRAFFIC_KEYS[kind])}, missing "
            f"{sorted(TRAFFIC_KEYS[kind] - keys)}")
    return traffic


def load_cell(root: Path, workload: str) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(has {sorted(cells)})")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    if "family" not in config:
        raise ValueError(
            f"{conf['file']} names no family: give it \"family\": "
            f"\"<name>\" for the file {family_file(root, '<name>')}")
    family = load_family(root, config["family"])
    family.check(config)
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    e2e_names = {m["name"] for m in e2e}
    per = [m for m in bench["per_layer"]
           if workload in m.get("workloads", [workload]
                                if m["moves"] in e2e_names else [])]
    return Cell(name=workload, config=config,
                traffic=load_traffic(root, w["traffic"]),
                chips=int(w["chips"]), end_to_end=e2e, per_layer=per,
                family=family)


def load_reader(root: Path, name: str) -> Callable:
    return _load_module(metric_file(root, name), "bench_metric_", name).read


def load_family(root: Path, name: str) -> ModuleType:
    """The family module ``bench/families/<name>.py``: ``check``,
    ``describe``, ``make_weights``, ``reference``, ``flops``, ``build`` and,
    where its client input is not ``bench.model.make_input``'s, its own
    ``make_input``."""
    path = family_file(root, name)
    if not path.is_file():
        raise FileNotFoundError(f"family {name!r} has no file {path}")
    return _load_module(path, "bench_family_", name)


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------


class System:
    """``PitGateway`` on a loopback listener plus one pipelined client.

    ``frac_bits`` overrides the configuration's fractional bits for the
    program alone (the lower-precision control); the reference always
    keeps the configuration's own. ``impl`` is the garbled-circuit
    executor both parties use: ``"auto"`` (the device executor) in every
    run, the host oracle ``"ref"`` in the CPU tests.
    """

    def __init__(self, cfg: dict, family: ModuleType, seq_len: int,
                 seed: int, *, gateway_pool: int,
                 frac_bits: Optional[int] = None, impl: str = "auto"):
        from repro.config import PrivacyConfig
        from repro.net import TcpListener
        from repro.serve import PitGateway, gateway_client

        priv = cfg["privacy"]
        self.pcfg = PrivacyConfig(
            he_t_bits=priv["he_t_bits"],
            frac_bits=frac_bits if frac_bits is not None
            else priv["frac_bits"],
            layernorm_offload=priv["layernorm_offload"])
        s_model, s_gw, s_client = M.seed_words(seed, 3)
        t = time.perf_counter()
        self.weights = family.make_weights(cfg, seed)
        self.weights_s = time.perf_counter() - t
        t = time.perf_counter()
        self.model = family.build(self.pcfg, cfg, self.weights, s_model)
        self.gateway = PitGateway(self.model, seq_len, seed=s_gw, impl=impl,
                                  max_sessions=1, pool_cap=gateway_pool)
        self.listener = TcpListener()
        self.loop = self.gateway.serve_listener(self.listener,
                                                timeout=WIRE_TIMEOUT_S)
        self.client = gateway_client(
            "127.0.0.1", self.listener.port, seed=s_client, impl=impl,
            timeout=WIRE_TIMEOUT_S)
        self.up_s = time.perf_counter() - t

    def wire(self, which: str) -> dict:
        """Bytes both ways on the client's ``which`` socket, each frame's
        payload and its length prefix, and frames both ways, from the
        transport's counters."""
        tr = getattr(self.client, which).transport
        frames = tr.frames_sent + tr.frames_recv
        return {"bytes": tr.bytes_sent + tr.bytes_recv
                + FRAME_PREFIX_BYTES * frames, "frames": frames}

    def gc_work(self) -> List[dict]:
        """Every garbled-circuit op of one request: its netlist's AND
        gates, input and output wires, and instances."""
        from repro.core.plan import GC_KINDS
        from repro.core.session import gc_net_for

        sh = self.gateway.shared
        out = []
        for op in sh.plan.ops:
            if op.kind in GC_KINDS:
                net = gc_net_for(sh.protocol, op)
                out.append({
                    "kind": op.kind, "netlist": net.name,
                    "and": int(net.and_count),
                    "inputs": len(net.garbler_inputs)
                    + len(net.evaluator_inputs),
                    "outputs": len(net.outputs),
                    "instances": int(sh.plan.gc_instances(op))})
        return out

    def counters(self) -> dict:
        return self.gateway.metrics()["counters"]

    def close(self) -> None:
        self.client.close()
        self.loop.stop()
        self.listener.close()
        self.gateway.close()
        self.gateway.join(timeout=60)
        self.loop.join(timeout=60)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


@dataclass
class Window:
    """What the measured window did, and what the readers see."""

    phase: str
    seconds: float = 0.0
    attempted: int = 0
    completed: int = 0
    failed: int = 0
    stopped_by: str = "time"
    wire: Dict[str, dict] = field(default_factory=dict)
    compiles: dict = field(default_factory=dict)
    outputs: list = field(default_factory=list)  # (input index, y)
    bundle_ids: list = field(default_factory=list)
    unit_s: list = field(default_factory=list)  # seconds of each unit


@dataclass
class Context:
    """Everything a metric reader may read."""

    cell: Cell
    window: Window
    setup_s: float
    gc_work: List[dict]
    flops_per_request: int
    peaks: dict
    spans: list = field(default_factory=list)
    client_tid: int = 0
    trace: Optional[dict] = None

    @property
    def units(self) -> int:
        """Requests served (online) or bundles made (offline)."""
        return self.window.completed


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def _wire_delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in a}


def _span_total(spans, name: str) -> float:
    return sum(s.elapsed_s for s in spans if s.name == name)


def run_cell(root: Path, cell: Cell, seed: int, seconds: float,
             trace: bool, t_start: float, device: dict, *,
             frac_bits: Optional[int] = None, impl: str = "auto") -> dict:
    """One run of ``cell``: set-up, window, metrics, check. Returns the
    result object that ``run.py`` prints as its last line."""
    from repro import obs

    cfg, traffic, family = cell.config, cell.traffic, cell.family
    phase = traffic["kind"]
    seq_len = int(traffic["seq_len"])
    inputs = functools.partial(
        getattr(family, "make_input", M.make_input), cfg, seq_len, seed)
    pool = int(cfg["pool_cap"])
    if phase == "online" and trace:
        pool = min(pool, int(traffic["trace_pool"]))
    peaks = peaks_for(device["kind"]) if device["platform"] == "tpu" else {}
    clock = compile_clock.CompileClock()
    obs.enable()
    log(f"cell {cell.name}: {cfg['name']} {family.describe(cfg)} "
        f"S={seq_len}; traffic {phase}; seed {seed}; pool {pool}")
    system = System(cfg, family, seq_len, seed, gateway_pool=pool + 1,
                    frac_bits=frac_bits, impl=impl)
    eng = system.client
    win = Window(phase=phase)
    spans: list = []
    tr = None
    gc_work: List[dict] = []
    counters: dict = {}
    try:
        log(f"set-up: weights {system.weights_s:.3f}s; gateway up "
            f"{system.up_s:.3f}s (plan, HE keys, handshake)")
        t = time.perf_counter()
        eng.preprocess(1)
        log(f"set-up: bundle 1 {time.perf_counter() - t:.3f}s "
            f"(netlists, plans, compiles, one bundle)")
        t = time.perf_counter()
        if phase == "online":
            _warm_online(eng, inputs, pool)
        log(f"set-up: warm-up and pool fill {time.perf_counter() - t:.3f}s")
        gc_work = system.gc_work()
        _log_setup(obs.current().finished_spans(), clock)
        if trace:
            obs.current().clear()
        else:
            obs.disable()
        client_tid = threading.get_ident()
        tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        try:
            win, anchor_ns, setup_s = _window(
                system, inputs, traffic, seconds, t_start, clock, tdir,
                pool)
            if trace:
                t = time.perf_counter()
                spans = obs.current().finished_spans()
                data = T.load(T.find_xplane(tdir))
                tr = T.reduce(data, host=T.HostSpans(
                    spans, anchor_ns - T.window_of(data)[0], client_tid))
                log(f"trace: read and reduced in "
                    f"{time.perf_counter() - t:.3f}s")
        finally:
            if tdir:
                shutil.rmtree(tdir, ignore_errors=True)
        device["memory_peak_bytes"] = _memory_peak()
        if phase == "offline":
            _spend_bundles(eng, inputs, seed, win, traffic)
        counters = system.counters()
    finally:
        obs.disable()
        system.close()
    ctx = Context(cell=cell, window=win, setup_s=setup_s, gc_work=gc_work,
                  flops_per_request=family.flops(cfg, seq_len),
                  peaks=peaks, spans=spans, client_tid=client_tid, trace=tr)
    specs = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for spec in specs:
        value = load_reader(root, spec["name"])(ctx)
        if value is not None:
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    checks = _check(cfg, family, system.weights, inputs, win, counters)
    result = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": win.attempted,
        "failed": win.failed,
        "metrics": metrics,
        "device": device,
    }
    if tr is not None:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
        log("trace: device ops by time " + json.dumps(
            T.top(tr["op_s"], 25)))
        log("trace: device modules by time " + json.dumps(
            T.top(tr["module_s"], 25)))
    log(f"window: {win.completed} of {win.attempted} {_unit(phase)} in "
        f"{win.seconds:.3f}s, failed {win.failed}, stopped by "
        f"{win.stopped_by}; compiles inside it {win.compiles}; seconds "
        f"each {[round(u, 3) for u in win.unit_s]}")
    log(f"gateway counters: {counters}")
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return result


def _unit(phase: str) -> str:
    return "requests" if phase == "online" else "bundles"


def _warm_online(eng, inputs, pool) -> None:
    """Warm the online shapes with one request on bundle 1 while the
    offline pair makes the window's ``pool`` bundles."""
    err: list = []

    def warm():
        try:
            t = time.perf_counter()
            eng.run(inputs(WARMUP_INPUT))
            log(f"set-up: warm-up request {time.perf_counter() - t:.3f}s")
        except BaseException as e:  # re-raised on the main thread
            err.append(e)

    th = threading.Thread(target=warm, name="bench-warmup")
    th.start()
    try:
        for _ in range(pool):
            eng.preprocess(1)
    finally:
        th.join()
    if err:
        raise err[0]


def _log_setup(spans, clock) -> None:
    log(f"set-up: netlist generation {_span_total(spans, 'netlist.build'):.3f}s "
        f"(both parties, summed over threads)")
    log(f"set-up: plan compile {_span_total(spans, 'netlist.plan'):.3f}s")
    c = clock.snapshot()
    log(f"set-up: XLA/Mosaic compile {c['seconds']:.3f}s over "
        f"{c['compiles']} backend compiles (persistent cache: "
        f"{c['cache_hits']} hits, {c['cache_misses']} misses)")


def _window(system, inputs, traffic, seconds, t_start, clock, tdir, pool):
    """Drive the client for ``seconds``: closed loop, one client. A unit
    of work starts only while time is left and a bundle (online) or room
    in the session's pool (offline) is there; the window closes when the
    last one started returns."""
    import jax

    eng = system.client
    phase = traffic["kind"]
    which = "online" if phase == "online" else "offline"
    win = Window(phase=phase)
    c0 = clock.snapshot()
    w0 = system.wire(which)
    rec = T.recording(tdir) if tdir else contextlib.nullcontext()
    with rec:
        anchor_ns = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(T.WINDOW):
            t0 = time.perf_counter()
            setup_s = t0 - t_start
            i = 0
            while time.perf_counter() - t0 < seconds:
                if phase == "online" and eng.pool_size() == 0:
                    win.stopped_by = "pool empty"
                    break
                if phase == "offline" and eng.pool_size() >= pool + 1:
                    win.stopped_by = "pool full"
                    break
                win.attempted += 1
                tu = time.perf_counter()
                try:
                    if phase == "online":
                        x = inputs(i)
                        with jax.profiler.TraceAnnotation("bench.request"):
                            y = eng.run(x)
                        win.outputs.append((i, y))
                    else:
                        with jax.profiler.TraceAnnotation("bench.bundle"):
                            win.bundle_ids.extend(eng.offline.preprocess(
                                int(traffic["bundles_per_call"])))
                except Exception as e:  # the session is broken: stop
                    win.failed += 1
                    win.stopped_by = f"failure: {type(e).__name__}: {e}"
                    break
                win.unit_s.append(time.perf_counter() - tu)
                win.completed += 1
                i += 1
            win.seconds = time.perf_counter() - t0
    win.wire = {which: _wire_delta(w0, system.wire(which))}
    c1 = clock.snapshot()
    win.compiles = {k: c1[k] - c0[k] for k in c0}
    return win, anchor_ns, setup_s


def _memory_peak() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def _spend_bundles(eng, inputs, seed, win, traffic) -> None:
    """Spend a sample of the window's bundles, drawn from the seed and
    always with the last one made, on requests whose outputs are
    checked: this covers the garbling and the table stream."""
    ids = list(win.bundle_ids)
    if not ids:
        return
    k = min(int(traffic["check_bundles"]), len(ids))
    rng = np.random.default_rng(M.seed_words(seed, 2)[1])
    pick = sorted(set([len(ids) - 1]) | set(
        rng.choice(len(ids) - 1, size=k - 1, replace=False).tolist()
        if k > 1 else []))
    for j in pick:
        x = inputs(j)
        try:
            y = eng.online.run(x, bundle_id=ids[j])
        except Exception as e:
            log(f"check request on bundle {ids[j]} failed: "
                f"{type(e).__name__}: {e}")
            win.failed += 1
            continue
        win.outputs.append((j, y))


def _check(cfg, family, weights, inputs, win, counters) -> dict:
    """The numbers that decide ``correct``, each with its limit."""
    frac = cfg["privacy"]["frac_bits"]
    gap = 0.0
    for i, y in win.outputs:
        ref = family.reference(cfg, weights, inputs(i), frac)
        y = np.asarray(y, np.float64)
        if y.shape != ref.shape or not np.isfinite(y).all():
            gap = NO_OUTPUT
            break
        gap = max(gap, float(np.abs(y - ref).max()))
    expected = win.completed if win.phase == "online" else len(win.outputs)
    lost = (counters.get("bundles_burned", 0)
            + abs(counters.get("bundles_consumed", 0) - expected
                  - (1 if win.phase == "online" else 0)))
    return {
        "max_abs_gap": {"value": gap if win.outputs else NO_OUTPUT,
                        "limit": cfg["check_limits"]["max_abs_gap"]},
        "failed_or_unchecked": {"value": win.failed + (
            0 if win.outputs else 1), "limit": 0},
        "bundles_unaccounted": {"value": lost, "limit": 0},
    }
