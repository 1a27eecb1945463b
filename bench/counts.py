"""Operations and bytes the benchmark divides by device time.

Kept with the benchmark so that every run counts them the same way.
"""

from __future__ import annotations

#: bytes of one garbled-circuit label (128 bits)
LABEL_BYTES = 16
#: bytes of one Half-Gate AND gate's garbled table (two ciphertexts)
TABLE_BYTES_PER_AND = 2 * LABEL_BYTES


def model_flops(cfg: dict, seq_len: int) -> int:
    """Floating-point operations of the configuration's plaintext forward
    pass over one (seq_len, d) input: 2 per multiply-accumulate of the
    four projections, the two MLP matmuls, QK^T and PV."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    per_layer = seq_len * (4 * d * d + 2 * d * f) + 2 * seq_len * seq_len * d
    return 2 * cfg["num_hidden_layers"] * per_layer


def gc_bytes(and_gates: int, inputs: int, outputs: int,
             instances: int) -> int:
    """Bytes one garbling or evaluation of ``instances`` copies of a
    netlist has to move at the least: its garbled tables, written or read
    once, and one label per input and output wire of each copy."""
    return instances * (TABLE_BYTES_PER_AND * and_gates
                        + LABEL_BYTES * (inputs + outputs))
