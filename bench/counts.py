"""Operations and bytes the benchmark divides by device time.

Kept with the benchmark so that every run counts them the same way. These
are the counts every family shares; a model's FLOP count is its family's
``flops`` (``bench/families/<family>.py``).
"""

from __future__ import annotations

#: bytes of one garbled-circuit label (128 bits)
LABEL_BYTES = 16
#: bytes of one Half-Gate AND gate's garbled table (two ciphertexts)
TABLE_BYTES_PER_AND = 2 * LABEL_BYTES


def gc_bytes(and_gates: int, inputs: int, outputs: int,
             instances: int) -> int:
    """Bytes one garbling or evaluation of ``instances`` copies of a
    netlist has to move at the least: its garbled tables, written or read
    once, and one label per input and output wire of each copy."""
    return instances * (TABLE_BYTES_PER_AND * and_gates
                        + LABEL_BYTES * (inputs + outputs))
