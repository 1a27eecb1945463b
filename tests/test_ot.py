"""IKNP OT extension (wire v2): the packed-word extension against a frozen
bit-per-byte reference, the OT functionality itself, and the column
transpose.

The reference below is the extension as it stood before its matrices were
kept as packed 64-bit words: one byte per bit, every batch's rows drawn
from freshly keyed Philox streams advanced to the batch's word offset. Its
base OT is the module's own (unchanged), so both sessions share keys and
every byte they produce must agree.
"""

import copy

import numpy as np
import pytest

from repro.core import ot as OT

# ---------------------------------------------------------------------------
# frozen reference: the bit-per-byte extension
# ---------------------------------------------------------------------------


def _ref_prg_bits(seed: bytes, word_offset: int, n: int) -> np.ndarray:
    nw = -(-n // 64)
    bg = np.random.Philox(key=int.from_bytes(seed, "little"))
    if word_offset:
        bg.advance(word_offset // 4)
    skip = word_offset % 4
    words = np.random.Generator(bg).integers(
        0, 1 << 64, size=nw + skip, dtype=np.uint64, endpoint=False)
    return np.unpackbits(words[skip:].view(np.uint8),
                         bitorder="little")[:n]


def _ref_pack_cols(bits: np.ndarray) -> np.ndarray:
    cols = np.packbits(np.ascontiguousarray(bits.T), axis=1,
                       bitorder="little")
    return np.ascontiguousarray(cols).view(np.uint32)


def _ref_unpack_cols(cols: np.ndarray) -> np.ndarray:
    rows = np.unpackbits(np.ascontiguousarray(cols).view(np.uint8),
                         axis=1, bitorder="little")
    return np.ascontiguousarray(rows.T)


class _RefReceiver:
    def __init__(self, rng: np.random.Generator):
        self._a = int.from_bytes(rng.bytes(OT._EXP_BYTES), "little") | 1
        self._k0 = self._k1 = None
        self._word_off = 0
        self._tweak = 0

    def base_msg_a(self) -> bytes:
        return pow(OT._MODP_G, self._a, OT._MODP_P).to_bytes(
            OT.BASE_OT_MSG_BYTES, "big")

    def absorb_base_b(self, data: bytes) -> None:
        a, p = self._a, OT._MODP_P
        A = pow(OT._MODP_G, a, p)
        A_neg_a = pow(pow(A, p - 2, p), a, p)
        k0, k1 = [], []
        for i in range(OT.KAPPA):
            B = int.from_bytes(
                data[i * OT.BASE_OT_MSG_BYTES: (i + 1) * OT.BASE_OT_MSG_BYTES],
                "big")
            Ba = pow(B, a, p)
            k0.append(OT._h_group(Ba))
            k1.append(OT._h_group(Ba * A_neg_a % p))
        self._k0, self._k1 = k0, k1

    def extend(self, choice_bits: np.ndarray):
        x = np.asarray(choice_bits, np.uint8).reshape(-1)
        n = x.size
        t_rows = np.stack([_ref_prg_bits(k, self._word_off, n)
                           for k in self._k0])
        v_rows = np.stack([_ref_prg_bits(k, self._word_off, n)
                           for k in self._k1])
        self._word_off += -(-n // 64)
        u_rows = t_rows ^ v_rows ^ x[None, :]
        return _ref_pack_cols(u_rows).tobytes(), _ref_pack_cols(t_rows)

    def receive(self, y_data: bytes, choice_bits: np.ndarray,
                t_cols: np.ndarray) -> np.ndarray:
        x = np.asarray(choice_bits, np.uint8).reshape(-1)
        n = x.size
        pairs = np.frombuffer(y_data, np.uint32).reshape(n, 2, 4)
        mask = OT._crh(t_cols, self._tweak)
        self._tweak += n
        return pairs[np.arange(n), x.astype(np.int64)] ^ mask


class _RefSender:
    def __init__(self, rng: np.random.Generator):
        self._s_bits = np.unpackbits(
            np.frombuffer(rng.bytes(OT.KAPPA // 8), np.uint8),
            bitorder="little")
        self._b = [int.from_bytes(rng.bytes(OT._EXP_BYTES), "little") | 1
                   for _ in range(OT.KAPPA)]
        self._ks = None
        self._s_block = None
        self._word_off = 0
        self._tweak = 0

    def base_msg_b(self, a_data: bytes) -> bytes:
        p = OT._MODP_P
        A = int.from_bytes(a_data, "big")
        fb_g = OT._FixedBase(OT._MODP_G, p)
        fb_a = OT._FixedBase(A, p)
        out = bytearray()
        ks = []
        for i in range(OT.KAPPA):
            B = fb_g.pow(self._b[i])
            if self._s_bits[i]:
                B = B * A % p
            out += B.to_bytes(OT.BASE_OT_MSG_BYTES, "big")
            ks.append(OT._h_group(fb_a.pow(self._b[i])))
        self._ks = ks
        self._s_block = _ref_pack_cols(
            self._s_bits[:, None].astype(np.uint8)).reshape(4)
        return bytes(out)

    def respond(self, u_data: bytes, n: int, zero_labels, r) -> bytes:
        u_rows = _ref_unpack_cols(
            np.frombuffer(u_data, np.uint32).reshape(n, 4))
        g_rows = np.stack([_ref_prg_bits(self._ks[i], self._word_off, n)
                           for i in range(OT.KAPPA)])
        self._word_off += -(-n // 64)
        q_rows = g_rows ^ (self._s_bits[:, None] & u_rows)
        q_cols = _ref_pack_cols(q_rows)
        z = np.asarray(zero_labels, np.uint32)
        one = z ^ np.broadcast_to(np.asarray(r, np.uint32), z.shape)
        y0 = z.reshape(n, 4) ^ OT._crh(q_cols, self._tweak)
        y1 = one.reshape(n, 4) ^ OT._crh(q_cols ^ self._s_block[None, :],
                                         self._tweak)
        self._tweak += n
        out = np.empty((n, 2, 4), np.uint32)
        out[:, 0] = y0
        out[:, 1] = y1
        return out.tobytes()


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _session(receiver_cls, sender_cls, seed: int):
    """A receiver/sender pair after the one-time base OT."""
    rcv = receiver_cls(np.random.default_rng([seed, 0]))
    snd = sender_cls(np.random.default_rng([seed, 1]))
    rcv.absorb_base_b(snd.base_msg_b(rcv.base_msg_a()))
    return rcv, snd


@pytest.fixture(scope="module")
def based():
    """Sessions after the base OT, made once per (classes, seed) and copied
    for each use: the base OT is pure-Python modexp, about a second."""
    made = {}

    def get(receiver_cls, sender_cls, seed):
        key = (receiver_cls, sender_cls, seed)
        if key not in made:
            made[key] = _session(receiver_cls, sender_cls, seed)
        return copy.deepcopy(made[key])

    return get


def _batch(rng: np.random.Generator, n: int):
    x = rng.integers(0, 2, n, dtype=np.uint8)
    z = rng.integers(0, 1 << 32, (n, 4), dtype=np.uint32)
    r = rng.integers(0, 1 << 32, 4, dtype=np.uint32)
    return x, z, r


def _run(rcv, snd, x, z, r):
    """One OT batch end to end: (u bytes, t columns, y bytes, labels)."""
    u, t_cols = rcv.extend(x)
    y = snd.respond(u, x.size, z, r)
    return u, t_cols, y, rcv.receive(y, x, t_cols)


# consecutive batch sizes: cross Philox's 4-word blocks and every word
# offset mod 4, with sizes that are and are not multiples of 8 and 64
CHAIN = (1, 7, 63, 64, 65, 257, 2688, 21504, 43008)

# ---------------------------------------------------------------------------
# tests
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("steps", range(1, len(CHAIN) + 1),
                         ids=[f"to_{n}" for n in CHAIN])
def test_iknp_byte_parity_with_bit_per_byte_reference(based, steps):
    """A session's batches CHAIN[:steps], one after the other: u bytes, t
    columns, y bytes and received labels equal the reference's at every
    step."""
    new = based(OT.IknpReceiver, OT.IknpSender, 14)
    ref = based(_RefReceiver, _RefSender, 14)
    rng = np.random.default_rng(steps)
    for n in CHAIN[:steps]:
        x, z, r = _batch(rng, n)
        got, want = _run(*new, x, z, r), _run(*ref, x, z, r)
        assert len(got[0]) == OT.ot_v2_request_bytes(n)
        assert len(got[2]) == OT.ot_v2_response_bytes(n)
        assert got[0] == want[0], f"u differs at n={n}"
        assert got[1].dtype == np.uint32 and got[1].shape == (n, 4)
        np.testing.assert_array_equal(got[1], want[1])
        assert got[2] == want[2], f"y differs at n={n}"
        np.testing.assert_array_equal(got[3], want[3])


@pytest.mark.parametrize("n", [1, 9, 130, 4099])
def test_iknp_receiver_gets_chosen_labels(based, n):
    """The OT functionality: received labels are z ^ (x * r)."""
    rcv, snd = based(OT.IknpReceiver, OT.IknpSender, 14)
    rng = np.random.default_rng(n)
    for _ in range(2):
        x, z, r = _batch(rng, n)
        labels = _run(rcv, snd, x, z, r)[3]
        want = z ^ (x[:, None].astype(np.uint32) * r[None, :])
        np.testing.assert_array_equal(labels, want)
        # the other label stays hidden: the pair's unchosen half is not it
        assert not np.array_equal(labels, z ^ ((1 - x[:, None]).astype(
            np.uint32) * r[None, :]))


@pytest.mark.parametrize("n", [0, 1, 8, 63, 65, 100, 1000, 4097])
def test_cols_transpose_matches_packbits(n):
    """(KAPPA, nw) uint64 rows -> (n, 4) uint32 columns, bit for bit."""
    nw = -(-n // 64)
    rows = np.random.default_rng(n).integers(
        0, 1 << 64, (OT.KAPPA, nw), dtype=np.uint64, endpoint=False)
    bits = np.unpackbits(rows.view(np.uint8), axis=1,
                         bitorder="little")[:, :n]
    want = np.packbits(np.ascontiguousarray(bits.T), axis=1,
                       bitorder="little").view(np.uint32)
    got = OT._cols(rows, n)
    assert got.shape == (n, 4) and got.dtype == np.uint32
    np.testing.assert_array_equal(got, want)


def test_iknp_sessions_from_equal_seeds_agree():
    """A fresh base OT restarts the streams: two sessions from equal seeds
    produce the same bytes batch for batch, and a session does not repeat
    its own first batch."""
    a = _session(OT.IknpReceiver, OT.IknpSender, seed=3)
    b = _session(OT.IknpReceiver, OT.IknpSender, seed=3)
    rng = np.random.default_rng(3)
    x, z, r = _batch(rng, 300)
    first = _run(*a, x, z, r)
    for got, want in zip(_run(*b, x, z, r), first):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    again = _run(*a, x, z, r)
    assert again[0] != first[0] and again[2] != first[2]
    np.testing.assert_array_equal(again[3], first[3])
