"""§Roofline table: read the dry-run artifacts and print per-cell terms."""

from __future__ import annotations

import glob
import json
import os

from benchmarks.common import emit


def main(dirname: str = "experiments/dryrun"):
    files = sorted(glob.glob(os.path.join(dirname, "*.json")))
    if not files:
        emit("roofline_missing", 0.0, "run launch/dryrun first")
        return
    for f in files:
        r = json.load(open(f))
        if not r.get("ok"):
            emit(f"roofline_{r['arch']}_{r['shape']}", 0.0,
                 f"FAILED:{r.get('error', '?')}")
            continue
        rl = r["roofline"]
        tag = "mp" if r["multi_pod"] else "sp"
        emit(
            f"roofline_{r['arch']}_{r['shape']}_{tag}",
            rl["step_lower_bound_s"] * 1e6,
            f"dom={rl['dominant']};compute_s={rl['compute_s']:.4f}"
            f";memory_s={rl['memory_s']:.4f}"
            f";collective_s={rl['collective_s']:.4f}"
            f";model/hlo={r['model_to_hlo_flops']:.2f}",
        )


if __name__ == "__main__":
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    main()
