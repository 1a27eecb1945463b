"""The check that decides ``correct``, driven through a whole run at a
small width on the CPU, with the chip lookup skipped: a sound run passes,
and the lower-precision control and each fault a serving cell can have
come out not correct.

The limit here is set from this size's own readings (sound 0.0472,
control 0.1474 at seed 2**31 + 12345; see PERF.md): the cells' limits
are set from their own readings at their widths.
"""

import time
from pathlib import Path

import numpy as np
import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**31 + 12345
SMALL_LIMIT = 0.09
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def small_cell(workload: str) -> harness.Cell:
    cell = harness.load_cell(ROOT, workload)
    cell.config.update(hidden_size=16, num_attention_heads=2, head_dim=8,
                       intermediate_size=32, pool_cap=1,
                       check_limits={"max_abs_gap": SMALL_LIMIT})
    cell.traffic["seq_len"] = 4
    return cell


def run(workload: str, **kw) -> dict:
    return harness.run_cell(ROOT, small_cell(workload), SEED, 1.0, False,
                            time.perf_counter(), dict(DEVICE), impl="ref",
                            **kw)


def test_sound_run_is_correct():
    res = run("bert-tiny.online")
    assert res["correct"], res["checks"]
    assert res["attempted"] == 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"online_s", "online_wire_mb", "setup_s"}
    assert list(res)[-1] == "checks"


def test_lower_precision_control_is_not_correct():
    cell = small_cell("bert-tiny.online")
    res = run("bert-tiny.online",
              frac_bits=cell.config["privacy"]["frac_bits"] - 1)
    assert not res["correct"]
    assert res["checks"]["max_abs_gap"]["value"] > SMALL_LIMIT


def _alter_one(s, p):
    """One element of the answer +1.0, where the server produces it."""
    s[0, 0] = (int(s[0, 0]) + (1 << p.frac)) % p.t


def _drop_half(s, p):
    """Half of the request's rows left out of the answer."""
    s[s.shape[0] // 2:] = 0


@pytest.mark.parametrize("fault", [_alter_one, _drop_half],
                         ids=["answer_altered", "half_the_rows_left_out"])
def test_answer_faults_are_not_correct(monkeypatch, fault):
    from repro.net import party

    send = party.EvaluatorEndpoint._send_sim

    def faulty(self, tag, payload, phase):
        if tag == "reveal":  # the server's share of the answer
            s = np.array(payload["s"], np.uint64)
            fault(s, self.shared.protocol)
            payload = {"s": s}
        return send(self, tag, payload, phase)

    monkeypatch.setattr(party.EvaluatorEndpoint, "_send_sim", faulty)
    res = run("bert-tiny.online")
    assert not res["correct"]
    assert res["checks"]["max_abs_gap"]["value"] > 0.5


def test_garbled_tables_altered_in_the_stream_are_not_correct(monkeypatch):
    from repro.net import wire

    pack = wire.pack_tables_delta

    def altered(tables):
        return pack(np.asarray(tables) ^ np.uint32(0x5A5A5A5A))

    monkeypatch.setattr(wire, "pack_tables_delta", altered)
    res = run("bert-tiny.offline")
    assert not res["correct"]
    assert res["attempted"] >= 1


def test_offline_sound_run_is_correct():
    res = run("bert-tiny.offline")
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"bundles_per_s", "offline_wire_mb",
                                   "setup_s"}
