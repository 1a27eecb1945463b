"""Simulated oblivious transfer + channel accounting.

Honest-but-curious simulation: both endpoints live in-process, but every
protocol message is metered so the benchmarks reproduce the paper's
communication columns. Cost model follows IKNP OT extension [11]: κ=128
bits per extended OT plus the chosen 128-bit label.

The byte constants and :func:`choose_labels` are shared with the real
two-party runtime (:mod:`repro.net`), which frames OT batches on the wire
at exactly the metered sizes — the in-process meter is the oracle the net
layer's ledger is asserted against.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict

import numpy as np


@dataclass
class Channel:
    client_to_server: int = 0
    server_to_client: int = 0
    rounds: int = 0
    by_tag: Dict[str, int] = field(default_factory=dict)

    def c2s(self, nbytes: int, tag: str = ""):
        self.client_to_server += int(nbytes)
        self.rounds += 1
        if tag:
            self.by_tag[tag] = self.by_tag.get(tag, 0) + int(nbytes)

    def s2c(self, nbytes: int, tag: str = ""):
        self.server_to_client += int(nbytes)
        self.rounds += 1
        if tag:
            self.by_tag[tag] = self.by_tag.get(tag, 0) + int(nbytes)

    @property
    def total(self) -> int:
        return self.client_to_server + self.server_to_client

    def time_s(self, bandwidth_bps: float = 9.6e9, latency_s: float = 0.165e-3,
               max_rounds: int = 0) -> float:
        """LAN model from the paper's setup (9.6 Gb/s, 0.165 ms)."""
        rounds = max_rounds if max_rounds else self.rounds
        return self.total * 8 / bandwidth_bps + rounds * latency_s


OT_MSG_BYTES = 16  # receiver's per-transfer IKNP column message
OT_BYTES_PER_TRANSFER = 2 * 16 + 16  # IKNP: 2 masked labels + correction


def ot_request_bytes(n: int) -> int:
    """Bytes of the receiver's choice-derived messages for ``n`` OTs."""
    return n * OT_MSG_BYTES


def ot_response_bytes(n: int) -> int:
    """Bytes of the sender's masked label pairs for ``n`` OTs."""
    return n * OT_BYTES_PER_TRANSFER


def choose_labels(zero_labels, r, choice_bits):
    """The OT functionality itself: labels for the receiver's choice bits.

    zero_labels: (..., 4) uint32; r: broadcastable; choice_bits (...,).
    Pure label algebra (no metering) — shared by the in-process simulation
    and the garbler side of the wire runtime.
    """
    import jax.numpy as jnp

    from repro.core import labels as LB

    bits = jnp.asarray(choice_bits, jnp.uint32)
    return LB.maybe_xor(zero_labels, bits, r)


def ot_labels(channel: Channel, zero_labels, r, choice_bits, tag="ot"):
    """Evaluator obtains labels for its choice bits; garbler learns nothing."""
    n = int(np.prod(choice_bits.shape))
    channel.c2s(ot_request_bytes(n), tag)  # receiver's OT messages
    channel.s2c(ot_response_bytes(n), tag)
    return choose_labels(zero_labels, r, choice_bits)


# ---------------------------------------------------------------------------
# IKNP OT extension (v2 wire format): real base OT + extension matrix
# ---------------------------------------------------------------------------
#
# Roles follow the GC protocol: the evaluator endpoint is the OT
# *receiver* (choice bits = its masked-input bits), the garbler the OT
# *sender* — so in IKNP's base phase the roles reverse: the receiver acts
# as base-OT sender of κ=128 seed pairs, the garbler as base-OT receiver
# with a secret selection string s.
#
# Base OTs are Chou–Orlandi over the RFC 3526 2048-bit MODP group
# (g = 2); H is SHA-256 truncated to a 16-byte PRG seed. The extension
# PRG is counter-mode Philox, the correlation-robust hash is the repo's
# ARX label hash — the same primitive stack as garbling itself.
#
# Wire cost per batch of n OTs: the column matrix u is exactly
# κ bits = 16 B per OT (receiver→sender, same as the old sim-OT request)
# and the masked pair (y0, y1) is 32 B per OT (sender→receiver, down
# from the sim's 48 B block) — plus the one-time base exchange below.

KAPPA = 128  # IKNP security parameter / number of base OTs
BASE_OT_MSG_BYTES = 256  # one 2048-bit group element
BASE_OT_A_BYTES = BASE_OT_MSG_BYTES
BASE_OT_B_BYTES = KAPPA * BASE_OT_MSG_BYTES
OT_V2_PAIR_BYTES = 2 * 16  # two masked 128-bit labels

# RFC 3526, group 14 (2048-bit MODP), generator 2.
_MODP_P = int(
    "FFFFFFFFFFFFFFFFC90FDAA22168C234C4C6628B80DC1CD129024E088A67CC74"
    "020BBEA63B139B22514A08798E3404DDEF9519B3CD3A431B302B0A6DF25F1437"
    "4FE1356D6D51C245E485B576625E7EC6F44C42E9A637ED6B0BFF5CB6F406B7ED"
    "EE386BFB5A899FA5AE9F24117C4B1FE649286651ECE45B3DC2007CB8A163BF05"
    "98DA48361C55D39A69163FA8FD24CF5F83655D23DCA3AD961C62F356208552BB"
    "9ED529077096966D670C354E4ABC9804F1746C08CA18217C32905E462E36CE3B"
    "E39E772C180E86039B2783A2EC07A28FB5C55DF06F4C52C9DE2BCBF695581718"
    "3995497CEA956AE515D2261898FA051015728E5A8AACAA68FFFFFFFFFFFFFFFF",
    16,
)
_MODP_G = 2


def _h_group(x: int) -> bytes:
    import hashlib

    return hashlib.sha256(x.to_bytes(BASE_OT_MSG_BYTES, "big")).digest()[:16]


# Short-exponent DH: 2·κ = 256-bit exponents in the 2048-bit group give
# the κ=128 target (Pollard-λ on an ℓ-bit exponent costs 2^{ℓ/2}), and
# cut the pure-python modexp cost ~8x versus full-size exponents.
_EXP_BYTES = 32


class _FixedBase:
    """Fixed-base modexp: precompute base^(2^i) once, then each random
    exponent costs ~ℓ/2 modmuls instead of a full square-and-multiply
    (the sender exponentiates the same two bases κ times)."""

    def __init__(self, base: int, p: int, bits: int = 8 * _EXP_BYTES):
        self.p = p
        pows = [base % p]
        for _ in range(bits - 1):
            pows.append(pows[-1] * pows[-1] % p)
        self.pows = pows

    def pow(self, e: int) -> int:
        acc, p, i = 1, self.p, 0
        while e:
            if e & 1:
                acc = acc * self.pows[i] % p
            e >>= 1
            i += 1
        return acc


def _streams(seeds) -> list:
    """One counter-mode Philox stream per 16-byte seed, kept for the
    session: each batch draws its rows' words where the last one ended."""
    return [np.random.Philox(key=int.from_bytes(k, "little")) for k in seeds]


def _draw_rows(streams, nw: int) -> np.ndarray:
    """(len(streams), nw) uint64: the next nw words of every stream. Bit j
    of a row (little-endian) belongs to OT j of the batch."""
    return np.stack([g.random_raw(nw) for g in streams])


def _transpose8(x: np.ndarray) -> np.ndarray:
    """Transpose each uint64 as an 8x8 bit block, in place: bit 8r+c <->
    bit 8c+r (three delta swaps)."""
    t = np.empty_like(x)
    for mask, sh in ((0x00AA00AA00AA00AA, 7), (0x0000CCCC0000CCCC, 14),
                     (0x00000000F0F0F0F0, 28)):
        sh = np.uint64(sh)
        np.right_shift(x, sh, out=t)
        t ^= x
        t &= np.uint64(mask)
        x ^= t
        t <<= sh
        x ^= t
    return x


def _cols(rows: np.ndarray, n: int) -> np.ndarray:
    """(KAPPA, nw) uint64 rows -> per-OT 128-bit columns (n, 4) uint32.

    Byte b of row 8c+k holds bit k of column byte c for OTs 8b..8b+7;
    gathering those eight row bytes into one word makes an 8x8 bit block
    whose transpose is the eight OTs' column byte c."""
    nb = 8 * rows.shape[1]  # bytes per row
    rb = rows.view(np.uint8).reshape(KAPPA // 8, 8, nb)  # (c, k, b)
    blocks = np.ascontiguousarray(rb.transpose(2, 0, 1))  # (b, c, k)
    blocks = _transpose8(blocks.view(np.uint64)).view(np.uint8)
    cols = blocks.reshape(-1, KAPPA // 8, 8).transpose(0, 2, 1)  # (b, m, c)
    cols = cols.reshape(-1, KAPPA // 8)[:n]
    return np.ascontiguousarray(cols).view(np.uint32)


def _crh(blocks: np.ndarray, tweak0: int) -> np.ndarray:
    """Correlation-robust hash of (n, 4) uint32 blocks (ARX label hash)."""
    from repro.kernels.halfgate import ref_np as HGNP

    n = blocks.shape[0]
    tweaks = (np.arange(tweak0, tweak0 + n) & 0xFFFFFFFF).astype(np.uint32)
    return np.asarray(HGNP.hash_labels(blocks, tweaks), np.uint32)


class IknpReceiver:
    """Evaluator side: base-OT sender, extension-matrix producer."""

    def __init__(self, rng: np.random.Generator):
        self._a = int.from_bytes(rng.bytes(_EXP_BYTES), "little") | 1
        self._t_streams = self._v_streams = None
        self._tweak = 0

    def base_msg_a(self) -> bytes:
        return pow(_MODP_G, self._a, _MODP_P).to_bytes(
            BASE_OT_MSG_BYTES, "big")

    def absorb_base_b(self, data: bytes) -> None:
        a, p = self._a, _MODP_P
        A = pow(_MODP_G, a, p)
        # k1 = (B/A)^a = B^a · A^{-a}: one modmul per OT on top of the
        # shared B^a, instead of a second full modexp
        A_neg_a = pow(pow(A, p - 2, p), a, p)
        k0, k1 = [], []
        for i in range(KAPPA):
            B = int.from_bytes(
                data[i * BASE_OT_MSG_BYTES: (i + 1) * BASE_OT_MSG_BYTES],
                "big")
            Ba = pow(B, a, p)
            k0.append(_h_group(Ba))
            k1.append(_h_group(Ba * A_neg_a % p))
        self._t_streams, self._v_streams = _streams(k0), _streams(k1)

    def extend(self, choice_bits: np.ndarray):
        """Choice bits -> (u column matrix bytes, private t columns)."""
        x = np.asarray(choice_bits, np.uint8).reshape(-1)
        n = x.size
        nw = -(-n // 64)
        xw = np.zeros(8 * nw, np.uint8)  # choice bits packed like a row
        xw[:-(-n // 8)] = np.packbits(x, bitorder="little")
        t_rows = _draw_rows(self._t_streams, nw)
        u_rows = t_rows ^ _draw_rows(self._v_streams, nw) ^ xw.view(np.uint64)
        return _cols(u_rows, n).tobytes(), _cols(t_rows, n)

    def receive(self, y_data: bytes, choice_bits: np.ndarray,
                t_cols: np.ndarray) -> np.ndarray:
        """Unmask the chosen labels: flat (n, 4) uint32."""
        x = np.asarray(choice_bits, np.uint8).reshape(-1)
        n = x.size
        pairs = np.frombuffer(y_data, np.uint32).reshape(n, 2, 4)
        mask = _crh(t_cols, self._tweak)
        self._tweak += n
        return pairs[np.arange(n), x.astype(np.int64)] ^ mask


class IknpSender:
    """Garbler side: base-OT receiver (secret s), masked-pair producer."""

    def __init__(self, rng: np.random.Generator):
        self._s_bits = np.unpackbits(
            np.frombuffer(rng.bytes(KAPPA // 8), np.uint8),
            bitorder="little")
        self._b = [int.from_bytes(rng.bytes(_EXP_BYTES), "little") | 1
                   for _ in range(KAPPA)]
        self._A = None
        self._g_streams = None
        self._s_block = None
        self._tweak = 0

    def base_msg_b(self, a_data: bytes) -> bytes:
        p = _MODP_P
        self._A = int.from_bytes(a_data, "big")
        # both bases are fixed across the κ exponentiations — amortize
        # the squaring chains once
        fb_g = _FixedBase(_MODP_G, p)
        fb_a = _FixedBase(self._A, p)
        out = bytearray()
        ks = []
        for i in range(KAPPA):
            B = fb_g.pow(self._b[i])
            if self._s_bits[i]:
                B = B * self._A % p
            out += B.to_bytes(BASE_OT_MSG_BYTES, "big")
            ks.append(_h_group(fb_a.pow(self._b[i])))
        self._g_streams = _streams(ks)
        self._s_block = np.packbits(self._s_bits,
                                    bitorder="little").view(np.uint32)
        return bytes(out)

    def respond(self, u_data: bytes, n: int, zero_labels,
                r) -> bytes:
        """u matrix + the (zero, one) label pairs -> masked pairs bytes.

        ``zero_labels``: (..., 4) with n leading elements; ``r``
        broadcastable FreeXOR offset. Output: n × (y0, y1) 32-byte pairs.
        """
        # q_j = g_j ^ (s & u_j) column by column: u stays as received
        u_cols = np.frombuffer(u_data, np.uint32).reshape(n, 4)
        g_cols = _cols(_draw_rows(self._g_streams, -(-n // 64)), n)
        q_cols = g_cols ^ (u_cols & self._s_block[None, :])
        z = np.asarray(zero_labels, np.uint32)
        one = z ^ np.broadcast_to(np.asarray(r, np.uint32), z.shape)
        lab0 = z.reshape(n, 4)
        lab1 = one.reshape(n, 4)
        y0 = lab0 ^ _crh(q_cols, self._tweak)
        y1 = lab1 ^ _crh(q_cols ^ self._s_block[None, :], self._tweak)
        self._tweak += n
        out = np.empty((n, 2, 4), np.uint32)
        out[:, 0] = y0
        out[:, 1] = y1
        return out.tobytes()


def ot_v2_request_bytes(n: int) -> int:
    """Receiver→sender extension-matrix bytes (κ bits per OT)."""
    return n * OT_MSG_BYTES


def ot_v2_response_bytes(n: int) -> int:
    """Sender→receiver masked-pair bytes (two 128-bit labels per OT)."""
    return n * OT_V2_PAIR_BYTES
