"""Every name in BENCHMARK.json and in the configurations resolves to its
file, each family's reference runs without the program, and the entry
point refuses to run without a TPU."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
FAMILIES = sorted(p.stem for p in (ROOT / "bench" / "families").glob("*.py"))
#: each family's small configuration for the tests
TINY = ROOT / "bench" / "tests" / "data" / "families"


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_resolves(entry):
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"]
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    for key in entry["reduced"]:
        assert key in cfg
    assert harness.family_file(ROOT, cfg["family"]).is_file()
    harness.load_family(ROOT, cfg["family"]).check(cfg)


#: imports a family with the program made unimportable, then draws its
#: weights and runs its reference on one input of its small configuration
NO_PROGRAM = """
import json, sys
sys.modules["repro"] = None
sys.path.insert(0, sys.argv[1])
from pathlib import Path
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from bench import harness, model
fam = harness.load_family(Path(sys.argv[1]), sys.argv[2])
cfg = json.loads(Path(sys.argv[3]).read_text())["tiny"]
fam.check(cfg)
w = fam.make_weights(cfg, 2**31 + 7)
x = getattr(fam, "make_input", model.make_input)(cfg, 4, 2**31 + 7, 0)
y = fam.reference(cfg, w, x, cfg["privacy"]["frac_bits"])
assert y.shape[0] == 4 and np.isfinite(y).all(), y
print("reference", y.shape)
"""


@pytest.mark.parametrize("family", FAMILIES)
def test_family_reference_runs_without_the_program(family):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", NO_PROGRAM, str(ROOT), family,
         str(TINY / f"{family}.json")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert proc.stdout.startswith("reference (4, ")


@pytest.mark.parametrize("entry", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_resolves(entry):
    cell = harness.load_cell(ROOT, entry["name"])
    assert cell.traffic["kind"] in ("online", "offline")
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    # a per-layer metric is reported only where what it moves is
    assert all(m["moves"] in names for m in cell.per_layer)


@pytest.mark.parametrize(
    "entry", BENCH["end_to_end"] + BENCH["per_layer"],
    ids=lambda m: m["name"])
def test_metric_resolves(entry):
    assert callable(harness.load_reader(ROOT, entry["name"]))


def test_device_kinds_have_peaks():
    for kind in ("TPU v5 lite",):
        assert harness.peaks_for(kind)["hbm_bytes_per_s"] > 0
    with pytest.raises(KeyError):
        harness.peaks_for("no such chip")


def test_run_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "bert-tiny.online",
         "--seed", str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
    assert "does not fall back" in proc.stderr


@pytest.mark.parametrize("extra", [{"clients": 4}, {"loop": "open"},
                                   {"kind": "burst"}])
def test_traffic_with_keys_the_harness_does_not_run_is_refused(
        tmp_path, extra):
    (tmp_path / "bench" / "traffic").mkdir(parents=True)
    mix = dict(json.loads(harness.traffic_file(ROOT, "online").read_text()),
               **extra)
    (tmp_path / "bench" / "traffic" / "mix.json").write_text(json.dumps(mix))
    with pytest.raises(ValueError):
        harness.load_traffic(tmp_path, "mix")


def test_wire_prefix_is_the_transports():
    from repro.net import transport

    assert harness.FRAME_PREFIX_BYTES == transport._LEN.size
