"""The host bitshuffle's word-level bit transpose against the format's
definition: the bit-array implementation it replaced, kept here as the
oracle.  Correctness only; no timing."""

import sys
import threading

import numpy as np
import pytest

from repro.core.precond import (bitshuffle, bitunshuffle, bitunshuffle_into,
                                undo_precond_into)


def bitshuffle_oracle(buf: bytes, itemsize: int) -> bytes:
    """The stored format: bit plane 8b+j (byte b, bit j, LSB first) of all N
    elements, packed LSB-first, ceil(N/8) bytes a plane, the last byte
    zero-padded; tail bytes (len % itemsize) passed through."""
    a = np.frombuffer(buf, dtype=np.uint8)
    n = a.size - (a.size % itemsize)
    body, tail = a[:n], a[n:]
    if n == 0:
        return tail.tobytes()
    elems = body.reshape(-1, itemsize)
    bits = np.unpackbits(elems, axis=1, bitorder="little")
    out = np.packbits(bits.T, axis=1, bitorder="little")
    return out.tobytes() + tail.tobytes()


# (itemsize, tail bytes): every itemsize with no tail, and the longest tail
# each larger itemsize allows
LAYOUTS = [(1, 0), (2, 0), (2, 1), (4, 0), (4, 3), (8, 0), (8, 7)]
COUNTS = [0, 1, 7, 8, 9, 1000, 262144]


@pytest.mark.parametrize("n_elems", COUNTS)
@pytest.mark.parametrize("itemsize,tail", LAYOUTS)
def test_bitshuffle_matches_oracle_and_inverts(itemsize, tail, n_elems):
    rng = np.random.default_rng(1000 * itemsize + 10 * tail + n_elems)
    x = rng.integers(0, 256, n_elems * itemsize + tail, dtype=np.uint8).tobytes()
    y = bitshuffle(x, itemsize)
    assert isinstance(y, bytes)
    assert y == bitshuffle_oracle(x, itemsize)
    back = bitunshuffle(y, itemsize, n_elems * itemsize)
    assert isinstance(back, bytes) and back == x
    if n_elems % 8 == 0 and tail == 0:
        assert bitunshuffle(y, itemsize) == x   # layout inferred


def test_bitshuffle_accepts_buffers():
    """ndarrays of any dtype and memoryviews are read as their bytes."""
    x = np.random.default_rng(7).standard_normal(1001).astype(np.float32)
    want = bitshuffle_oracle(x.tobytes(), 4)
    assert bitshuffle(x, 4) == want
    assert bitshuffle(memoryview(x.tobytes()), 4) == want


# (itemsize, n_elems, tail): counts off a multiple of 8 with a tail at every
# itemsize the checkpoints store (bf16, f32, f64), and single-word planes
# (n_elems <= 8, so ceil(N/8) == 1)
INTO_LAYOUTS = [(4, 9, 3), (4, 1000, 0), (4, 262144, 1),
                (2, 9, 1), (2, 1003, 1), (2, 262147, 1),
                (8, 9, 7), (8, 1003, 5), (8, 65541, 3),
                (2, 5, 1), (4, 8, 0), (8, 1, 7)]


@pytest.mark.parametrize("itemsize,n_elems,tail", INTO_LAYOUTS)
@pytest.mark.parametrize("dest", ["ndarray", "memoryview"])
@pytest.mark.parametrize("via_spec", [False, True])
def test_bitunshuffle_into_fills_offset_slice_exactly(itemsize, n_elems, tail,
                                                      dest, via_spec):
    rng = np.random.default_rng(100 * itemsize + n_elems + tail)
    x = rng.integers(0, 256, itemsize * n_elems + tail, dtype=np.uint8).tobytes()
    y = bitshuffle_oracle(x, itemsize)
    offset, spare = 13, 29
    big = np.full(offset + len(x) + spare, 0xA5, np.uint8)
    out = big[offset:] if dest == "ndarray" else memoryview(big)[offset:]
    if via_spec:
        written = undo_precond_into(f"bitshuffle{itemsize}", y, out, len(x))
    else:
        written = bitunshuffle_into(y, itemsize, out, itemsize * n_elems)
    assert written == len(x)
    assert big[offset:offset + len(x)].tobytes() == x
    assert (big[:offset] == 0xA5).all() and (big[offset + len(x):] == 0xA5).all()


def test_bitunshuffle_into_refuses_readonly_and_strided_out():
    x = np.random.default_rng(3).integers(0, 256, 400, dtype=np.uint8).tobytes()
    y = bitshuffle(x, 4)
    with pytest.raises(ValueError, match="read-only"):
        bitunshuffle_into(y, 4, memoryview(bytes(400)), 400)
    frozen = np.zeros(400, np.uint8)
    frozen.flags.writeable = False
    with pytest.raises(ValueError, match="read-only"):
        undo_precond_into("bitshuffle4", y, frozen, 400)
    strided = np.zeros((200, 4), np.uint8)[:, :2]       # 400 B, not contiguous
    with pytest.raises(ValueError, match="contiguous"):
        bitunshuffle_into(y, 4, strided, 400)
    with pytest.raises(ValueError, match="contiguous"):
        undo_precond_into("bitshuffle4", y, strided, 400)


@pytest.mark.parametrize("itemsize,dtype", [(2, np.float16), (4, np.float32)])
def test_concurrent_bitunshuffle_into_one_array(itemsize, dtype):
    """Four threads decode different baskets into disjoint slices of one
    array at once; every slice comes back bit for bit."""
    n_threads, rounds, size = 4, 8, itemsize * 65536 + itemsize - 1
    rng = np.random.default_rng(11)
    blocks = [rng.standard_normal(size // itemsize).astype(dtype).tobytes()
              + bytes(rng.integers(0, 256, size % itemsize, dtype=np.uint8))
              for _ in range(n_threads)]
    shuffled = [bitshuffle_oracle(b, itemsize) for b in blocks]
    dest = np.zeros(n_threads * size, np.uint8)
    start = threading.Barrier(n_threads)
    errors = []

    def decode(i):
        try:
            start.wait(timeout=30)
            for _ in range(rounds):
                undo_precond_into(f"bitshuffle{itemsize}", shuffled[i],
                                  dest[i * size:(i + 1) * size], size)
        except Exception as e:      # surfaced by the assertion below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=decode, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
    assert dest.tobytes() == b"".join(blocks)
