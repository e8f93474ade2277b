"""Blob stores, simulated cloud, and the superpost compaction codec."""

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.index import codec
from repro.storage import (InMemoryBlobStore, LocalBlobStore, NetworkModel,
                           RangeRequest, SimCloudStore)


# ---------------------------------------------------------------- blobstore
@pytest.mark.parametrize("make", [
    InMemoryBlobStore, lambda: LocalBlobStore(_tmpdir())])
def test_blobstore_roundtrip(make):
    store = make()
    store.put("a/b/blob1", b"hello world")
    assert store.get("a/b/blob1") == b"hello world"
    assert store.get_range(RangeRequest("a/b/blob1", 6, 5)) == b"world"
    assert store.size("a/b/blob1") == 11
    assert store.list("a/") == ["a/b/blob1"]
    store.delete("a/b/blob1")
    assert store.list() == []


def _tmpdir():
    import tempfile
    return tempfile.mkdtemp()


def test_local_store_atomic_overwrite():
    store = LocalBlobStore(_tmpdir())
    store.put("x", b"v1")
    store.put("x", b"v2")
    assert store.get("x") == b"v2"
    assert store.list() == ["x"]


def test_blob_name_escape_rejected():
    store = LocalBlobStore(_tmpdir())
    with pytest.raises(ValueError):
        store.put("../escape", b"nope")


# ----------------------------------------------------------------- simcloud
def test_simcloud_deterministic():
    base = InMemoryBlobStore()
    base.put("b", b"x" * 1000)
    reqs = [RangeRequest("b", 0, 100)] * 8
    s1 = SimCloudStore(base, seed=7)
    s2 = SimCloudStore(base, seed=7)
    _, st1 = s1.fetch_batch(reqs)
    _, st2 = s2.fetch_batch(reqs)
    assert st1.elapsed_s == st2.elapsed_s


def test_simcloud_affine_latency():
    """Fig. 2: latency flat until ~MBs, then linear in bytes."""
    base = InMemoryBlobStore()
    base.put("b", b"x" * (64 << 20))
    model = NetworkModel(jitter_sigma=0.0, tail_prob=0.0)
    cloud = SimCloudStore(base, model=model, seed=0)
    t_small = cloud.fetch(RangeRequest("b", 0, 1024))[1].elapsed_s
    t_2mb = cloud.fetch(RangeRequest("b", 0, 2 << 20))[1].elapsed_s
    t_32mb = cloud.fetch(RangeRequest("b", 0, 32 << 20))[1].elapsed_s
    assert t_small == pytest.approx(model.first_byte_s, rel=0.05)
    assert t_2mb < 2 * t_small                  # still latency-dominated
    assert t_32mb > 5 * t_small                 # bandwidth-dominated


def test_simcloud_parallel_beats_sequential():
    """The paper's core claim, in miniature: one batch of n parallel
    requests is far faster than n dependent round trips."""
    base = InMemoryBlobStore()
    base.put("b", b"x" * 10000)
    reqs = [RangeRequest("b", i * 100, 100) for i in range(16)]
    cloud = SimCloudStore(base, seed=0)
    _, par = cloud.fetch_batch(reqs)
    _, seq = cloud.fetch_chain(reqs)
    assert seq.elapsed_s > 5 * par.elapsed_s


def test_simcloud_hedging_cuts_tail():
    """§IV-G: issue L+, wait for L — tail latency drops."""
    base = InMemoryBlobStore()
    base.put("b", b"x" * 10000)
    model = NetworkModel(tail_prob=0.2, tail_scale=20.0)
    lat_all, lat_hedged = [], []
    for seed in range(300):
        c = SimCloudStore(base, model=model, seed=seed)
        reqs = [RangeRequest("b", 0, 100)] * 6
        lat_all.append(c.fetch_batch(reqs)[1].elapsed_s)
        c2 = SimCloudStore(base, model=model, seed=seed)
        lat_hedged.append(c2.fetch_batch(reqs, wait_for=4)[1].elapsed_s)
    assert np.percentile(lat_hedged, 95) < 0.6 * np.percentile(lat_all, 95)
    assert np.mean(lat_hedged) < np.mean(lat_all)


def test_simcloud_concurrency_queueing():
    base = InMemoryBlobStore()
    base.put("b", b"x" * 1000)
    model = NetworkModel(jitter_sigma=0.0, tail_prob=0.0)
    reqs = [RangeRequest("b", 0, 10)] * 64
    wide = SimCloudStore(base, model=model, concurrency=64, seed=0)
    narrow = SimCloudStore(base, model=model, concurrency=4, seed=0)
    t_wide = wide.fetch_batch(reqs)[1].elapsed_s
    t_narrow = narrow.fetch_batch(reqs)[1].elapsed_s
    assert t_narrow == pytest.approx(16 * t_wide, rel=0.05)


# -------------------------------------------------------------------- codec
@settings(max_examples=40, deadline=None)
@given(st.lists(st.integers(0, 2**50), max_size=200))
def test_varint_roundtrip(values):
    arr = np.asarray(sorted(values), dtype=np.uint64)
    data = codec.encode_varints(arr)
    out, used = codec.decode_varints(data, len(arr))
    assert used == len(data)
    assert (out == arr).all()


@settings(max_examples=30, deadline=None)
@given(st.data())
def test_superpost_roundtrip(data):
    n = data.draw(st.integers(0, 300))
    rng = np.random.default_rng(data.draw(st.integers(0, 2**16)))
    keys = np.unique(rng.integers(0, 2**45, size=n).astype(np.uint64))
    lengths = rng.integers(1, 10_000, size=len(keys)).astype(np.uint64)
    blob = codec.encode_superpost(keys, lengths)
    k2, l2 = codec.decode_superpost(blob)
    assert (k2 == keys).all() and (l2 == lengths).all()


def test_posting_key_split():
    blob_keys = np.array([0, 3, 70000])
    offsets = np.array([0, 12345, (1 << 40) - 1])
    keys = codec.posting_key(blob_keys, offsets)
    b, o = codec.split_posting_key(keys)
    assert (b == blob_keys).all() and (o == offsets).all()


def test_pointers_roundtrip():
    ptrs = [codec.BinPointer(i % 3, i * 17, i + 1) for i in range(100)]
    out = codec.unpack_pointers(codec.pack_pointers(ptrs))
    assert out == ptrs


def test_header_roundtrip_and_magic():
    payload = {"spec": {"B": 10, "L": 2}, "names": ["a", "b"]}
    data = codec.encode_header(payload)
    assert codec.decode_header(data) == payload
    with pytest.raises(ValueError):
        codec.decode_header(b"XXXX" + data[4:])


def test_superpost_compression_beats_raw():
    """Delta-varint must beat 16-byte raw (key, length) pairs on
    clustered postings (the paper's compression claim)."""
    rng = np.random.default_rng(0)
    offsets = np.sort(rng.integers(0, 1 << 24, size=1000).astype(np.uint64))
    keys = codec.posting_key(np.zeros(1000, np.uint64), offsets)
    lengths = rng.integers(50, 300, size=1000).astype(np.uint64)
    blob = codec.encode_superpost(keys, lengths)
    assert len(blob) < 0.5 * 16 * 1000


# ------------------------------------------- the decoders against LEB128
def _leb128(data: bytes, count: int) -> tuple[list[int], int]:
    """Plain LEB128, a byte at a time: the first `count` values of
    `data` and the bytes they take."""
    values, pos = [], 0
    for _ in range(count):
        value = shift = 0
        while True:
            if pos == len(data):
                raise ValueError("truncated")
            byte = data[pos]
            pos += 1
            value |= (byte & 0x7F) << shift
            shift += 7
            if byte < 0x80:
                break
        values.append(value)
    return values, pos


def _superpost_oracle(data: bytes) -> tuple[list[int], list[int]]:
    """A superpost read with `_leb128`: its count, then the key deltas,
    then the lengths; the keys are the running sum of the deltas."""
    (count,), pos = _leb128(data, 1)
    values, _ = _leb128(data[pos:], 2 * count)
    keys, total = [], 0
    for delta in values[:count]:
        total += delta
        keys.append(total)
    return keys, values[count:]


def _width(w: int) -> tuple[int, int]:
    """The least and the largest value LEB128 writes in `w` bytes."""
    return 1 << 7 * (w - 1), min((1 << 7 * w) - 1, 2**64 - 1)


def _case(values, keys, lengths, tail=b"", truncate=False):
    return (list(values), list(keys), list(lengths), tail, truncate)


_RNG = np.random.default_rng(18)
_BLOBS = np.sort(codec.posting_key(
    np.repeat([0, 1, 2, 7, 300], 40),
    np.concatenate([np.sort(_RNG.choice(1 << 40, 40, replace=False))
                    for _ in range(5)])))
DECODE_CASES = {
    # a value of each width, mixed with one-byte values; keys whose
    # deltas and lengths take that width
    **{f"width-{w}": _case([_width(w)[0], _width(w)[1], 3, _width(w)[0]],
                           [_width(w)[0], _width(w)[1]],
                           [_width(w)[1], _width(w)[0]])
       for w in range(1, 11)},
    "u64-max": _case([2**64 - 1, 0, 2**64 - 1], [2**64 - 1], [2**64 - 1]),
    # keys of several blobs: the delta across a blob is `blob_key << 40`
    # wide
    "across-blobs": _case(_BLOBS.tolist(), _BLOBS.tolist(),
                          _RNG.integers(1, 400, len(_BLOBS)).tolist()),
    "count-0": _case([], [], []),
    "count-1": _case([300], [12345], [77]),
    "one-byte": _case(range(128), range(128), range(127, -1, -1)),
    "two-byte": _case(range(128, 16384, 97),
                      np.cumsum(np.arange(128, 16384, 97)).tolist(),
                      range(16383, 127, -97)),
    "trailing-bytes": _case([5, 1 << 30, 6], [9, 1 << 33, (1 << 33) + 1],
                            [4, 300, 2**40], tail=b"\x05\x81\x01\xff"),
    "truncated": _case([1 << 20, 1 << 40], [3, 1 << 35], [1 << 20, 9],
                       truncate=True),
}


@pytest.mark.parametrize("case", list(DECODE_CASES))
def test_decoders_match_plain_leb128(case):
    """`decode_varints` (values and bytes consumed) and
    `decode_superpost` read what a byte-at-a-time LEB128 reads, as u64,
    and raise `ValueError` on a stream cut short."""
    values, keys, lengths, tail, truncate = DECODE_CASES[case]
    stream = codec.encode_varints(np.asarray(values, dtype=np.uint64))
    post = codec.encode_superpost(np.asarray(keys, dtype=np.uint64),
                                  np.asarray(lengths, dtype=np.uint64))
    if truncate:
        with pytest.raises(ValueError):
            codec.decode_varints(stream[:-1], len(values))
        with pytest.raises(ValueError):
            codec.decode_superpost(post[:-1])
        return
    want, used = _leb128(stream + tail, len(values))
    got, consumed = codec.decode_varints(stream + tail, len(values))
    assert got.dtype == np.uint64 and got.tolist() == want == values
    assert consumed == used == len(stream)
    # a prefix stops where its last value ends
    k = len(values) // 2
    got, consumed = codec.decode_varints(stream + tail, k)
    assert (got.tolist(), consumed) == _leb128(stream, k)
    want_keys, want_lengths = _superpost_oracle(post + tail)
    got_keys, got_lengths = codec.decode_superpost(post + tail)
    assert got_keys.dtype == got_lengths.dtype == np.uint64
    assert got_keys.tolist() == want_keys == keys
    assert got_lengths.tolist() == want_lengths == lengths
