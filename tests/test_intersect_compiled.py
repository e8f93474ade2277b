"""The intersect kernels on the chip's compiler, and which mode runs them.

Part one compiles every entry point for a described TPU v5e at the
widths a 1M-document deployment reaches (W = 31,250 words, padded to
its class of 32,768 by the batch ops, Q = 64 queries, L = S = 16, G = 16
shard units; and the class a service warms for batches of up to 16
jobs) — nothing runs, but Mosaic
refuses here whatever it would refuse on the chip, so these tests guard
the compiled path on every CPU run. The topology is described inside a
module fixture, never at import, and compiles run with the persistent
compilation cache off (a cache entry compiled for a described chip
cannot be read back without one).

Part two pins the single interpret decision in `ops.py`: interpreted
on the CPU, compiled on a TPU, a typed error anywhere else.

Part three checks every entry point bit-exact against the pure-jnp
refs on the default backend — the Pallas interpreter here, Mosaic on a
TPU — including a sweep of the cluster-fused kernel.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.intersect import (OP_AND, OP_ANDNOT, OP_OR,
                                     UnsupportedBackendError,
                                     combine_batch, combine_batch_ref,
                                     combine_cluster, combine_cluster_ref,
                                     intersect, intersect_ref,
                                     interpret_mode, launch_class,
                                     pack_cluster_programs, pack_programs,
                                     postings_to_bitmap,
                                     postings_to_bitmap_batch)
from repro.kernels.intersect.kernel import (combine_batch_pallas,
                                            combine_cluster_pallas)
from repro.kernels.intersect.ops import pad_to_shape_class, shape_class

W_1M = (1_000_000 + 31) // 32          # words of a 1M-document universe
Q, L, S, G = 64, 16, 16, 16
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def v5e_chip():
    """One chip of a described v5e:2x2 (no TPU needs to be attached)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                               # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(kernel):
    """The op's entry point and operand shapes: the one-query op as given,
    each batch op's kernel at the class its wrapper pads a 1M-document
    unit's batch to (`launch_class`), and `combine_batch` at the class a
    service warms for that unit's batches of up to 16 one-step jobs."""
    u32, i32 = jnp.uint32, jnp.int32
    c = launch_class(Q, L, W_1M, S, n_docs=1_000_000)
    Qc, Lc, Wc, Sc = c["Qc"], c["Lc"], c["Wc"], c["Sc"]
    w = launch_class(16, 1, 1, 1, n_docs=1_000_000)
    return {
        "intersect": (intersect, [((L, W_1M), u32)]),
        "combine_batch_warm": (_compiled(combine_batch_pallas),
                               [((w["Qc"], w["Lc"], w["Wc"]), u32),
                                ((w["Qc"], w["Sc"], 3), i32)]),
        "combine_batch": (_compiled(combine_batch_pallas),
                          [((Qc, Lc, Wc), u32), ((Qc, Sc, 3), i32)]),
        "combine_cluster": (_compiled(combine_cluster_pallas),
                            [((G, Qc, Lc, Wc), u32), ((G, Qc, Sc, 3), i32)]),
    }[kernel]


def _compiled(entry):
    return functools.partial(entry, interpret=False)


@pytest.mark.parametrize("kernel", ["intersect", "combine_batch_warm",
                                    "combine_batch", "combine_cluster"])
def test_kernel_compiles_for_v5e(kernel, v5e_chip, no_compile_cache,
                                 monkeypatch):
    """Each public op, steered onto its TPU branch, lowers to a Mosaic
    custom call and fits one v5e chip's HBM, at the shape its wrapper
    launches a 1M-document batch at."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    op, args = _shapes(kernel)
    specs = [jax.ShapeDtypeStruct(shape, dt, sharding=v5e_chip)
             for shape, dt in args]
    compiled = jax.jit(op).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < V5E_HBM_BYTES


def test_oversized_program_batch_splits_for_smem(v5e_chip, no_compile_cache,
                                                monkeypatch):
    """Programs beyond a v5e's 1 MiB of SMEM split over several calls."""
    from repro.kernels.intersect.kernel import PROGRAM_WORDS
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n_q, n_s = 8192, 32
    assert n_q * n_s * 3 > 2 * PROGRAM_WORDS
    c = launch_class(n_q, 2, 128, n_s, n_docs=128 * 32)
    specs = [jax.ShapeDtypeStruct((c["Qc"], c["Lc"], c["Wc"]), jnp.uint32,
                                  sharding=v5e_chip),
             jax.ShapeDtypeStruct((c["Qc"], c["Sc"], 3), jnp.int32,
                                  sharding=v5e_chip)]
    text = jax.jit(_compiled(combine_batch_pallas)).lower(
        *specs).compile().as_text()
    assert text.count("tpu_custom_call") >= 2


def test_program_batch_split_matches_ref(monkeypatch):
    """The split path is bit-exact (interpreted, with a tiny budget)."""
    from repro.kernels.intersect import kernel
    monkeypatch.setattr(kernel, "PROGRAM_WORDS", 7 * 3 * 3)
    rng = np.random.default_rng(8)
    n_q, n_l, n_docs = 17, 3, 3000
    bm = postings_to_bitmap_batch(
        [_random_postings(rng, n_l, n_docs) for _ in range(n_q)], n_docs)
    packed = pack_programs(_random_programs(rng, n_q, n_l), n_l)
    kernel.combine_batch_pallas.clear_cache()      # retrace with the patch
    out_p, cnt_p = combine_batch(bm, packed, n_docs=n_docs)
    kernel.combine_batch_pallas.clear_cache()      # and drop that trace
    out_r, cnt_r = combine_batch_ref(bm, packed)
    assert (np.asarray(out_p) == np.asarray(out_r)).all()
    assert (np.asarray(cnt_p) == np.asarray(cnt_r)).all()


# ------------------------------------------------------ interpret decision
def test_ops_run_interpreted_on_cpu():
    assert jax.default_backend() == "cpu"
    assert interpret_mode() is True
    bm = np.full((2, 300), 0xF0F0F0F0, dtype=np.uint32)
    hlo = jax.jit(intersect).lower(bm).as_text()
    assert "tpu_custom_call" not in hlo
    out, cnt = intersect(bm)
    assert int(cnt) == 300 * 16


@pytest.mark.parametrize("backend,interpret", [("tpu", False),
                                               ("cpu", True)])
def test_interpret_mode_follows_backend(backend, interpret, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert interpret_mode() is interpret


@pytest.mark.parametrize("backend", ["gpu", "METAL"])
def test_unknown_backend_raises(backend, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    bm = np.ones((2, 64), dtype=np.uint32)
    with pytest.raises(UnsupportedBackendError, match=backend):
        intersect(bm)
    with pytest.raises(UnsupportedBackendError):
        combine_batch(bm[None], pack_programs([[(OP_AND, 0, 1)]], 2),
                      n_docs=64 * 32)
    # the explicit oracle stays available on any backend
    out, _cnt = intersect(bm, impl="ref")
    assert (np.asarray(out) == 1).all()


def _random_postings(rng, L, n_docs):
    return [np.unique(rng.integers(0, n_docs, max(n_docs // 3, 2)))
            .astype(np.uint32) for _ in range(L)]


def _random_programs(rng, Q, L):
    """One random well-formed combine program per query."""
    progs = []
    for _ in range(Q):
        steps = []
        n_steps = int(rng.integers(0, L))
        for s in range(n_steps):
            op = int(rng.choice([OP_AND, OP_OR, OP_ANDNOT]))
            hi = L + s        # slots written so far: leaves + prior steps
            a = L + s - 1 if s else int(rng.integers(0, hi))
            steps.append((op, a, int(rng.integers(0, hi))))
        progs.append(steps)
    return progs


# ------------------------------------------ parity on the default backend
@pytest.mark.parametrize("L,n_docs", [(1, 100), (3, 40_000), (4, 2048)])
def test_intersect_vs_ref(L, n_docs):
    rng = np.random.default_rng(L + n_docs)
    bm = postings_to_bitmap(_random_postings(rng, L, n_docs), n_docs)
    out_c, cnt_c = intersect(bm, impl="pallas")
    out_r, cnt_r = intersect_ref(bm)
    assert (np.asarray(out_c) == np.asarray(out_r)).all()
    assert int(cnt_c) == int(cnt_r)


@pytest.mark.parametrize("program,Q,L,n_docs", [
    ("random", 4, 3, 5000), ("random", 7, 4, 40_000),
    ("and_chain", 1, 2, 100), ("and_chain", 5, 3, 33_000)])
def test_combine_batch_vs_ref(program, Q, L, n_docs):
    """Random programs, and the AND chain over every layer: the L-way
    intersection, which must also equal `intersect_ref`'s per query."""
    rng = np.random.default_rng(Q * 13 + L if program == "random"
                                else Q * 7 + L)
    bm = postings_to_bitmap_batch(
        [_random_postings(rng, L, n_docs) for _ in range(Q)], n_docs)
    programs = _random_programs(rng, Q, L) if program == "random" else \
        [[(OP_AND, L + s - 1 if s else 0, s + 1) for s in range(L - 1)]] * Q
    packed = pack_programs(programs, L)
    out_c, cnt_c = combine_batch(bm, packed, impl="pallas",
                                 n_docs=n_docs)
    out_r, cnt_r = combine_batch_ref(bm, packed)
    assert (np.asarray(out_c) == np.asarray(out_r)).all()
    assert (np.asarray(cnt_c) == np.asarray(cnt_r)).all()
    if program == "and_chain":
        for q in range(Q):
            want, want_cnt = intersect_ref(jnp.asarray(bm[q]))
            assert (np.asarray(out_c[q]) == np.asarray(want)).all()
            assert int(cnt_c[q]) == int(want_cnt)


@pytest.mark.parametrize("G,Q,L,n_docs", [(3, 4, 3, 5000), (8, 2, 2, 2048)])
def test_combine_cluster_vs_ref(G, Q, L, n_docs):
    rng = np.random.default_rng(G * 31 + Q)
    bm = np.stack([postings_to_bitmap_batch(
        [_random_postings(rng, L, n_docs) for _ in range(Q)], n_docs)
        for _ in range(G)])
    packed = pack_cluster_programs(
        [_random_programs(rng, Q, L) for _ in range(G)], L)
    out_c, cnt_c = combine_cluster(bm, packed, impl="pallas",
                                   n_docs=n_docs)
    out_r, cnt_r = combine_cluster_ref(bm, packed)
    assert (np.asarray(out_c) == np.asarray(out_r)).all()
    assert (np.asarray(cnt_c) == np.asarray(cnt_r)).all()


@pytest.mark.parametrize("G,Q,L,n_docs", [(1, 1, 1, 31), (2, 3, 2, 100),
                                          (4, 2, 3, 5000), (3, 5, 4, 40_000)])
def test_cluster_interpret_vs_ref(G, Q, L, n_docs):
    """Fused (shard, query, tile)-grid kernel vs ref, every platform."""
    rng = np.random.default_rng(G * 100 + Q * 10 + L)
    bm = np.stack([postings_to_bitmap_batch(
        [_random_postings(rng, L, n_docs) for _ in range(Q)], n_docs)
        for _ in range(G)])
    packed = pack_cluster_programs(
        [_random_programs(rng, Q, L) for _ in range(G)], L)
    out_p, cnt_p = combine_cluster(bm, packed, impl="pallas",
                                   n_docs=n_docs)
    out_r, cnt_r = combine_cluster_ref(bm, packed)
    assert out_p.shape == (G, Q, bm.shape[-1])
    assert cnt_p.shape == (G, Q)
    assert (np.asarray(out_p) == np.asarray(out_r)).all()
    assert (np.asarray(cnt_p) == np.asarray(cnt_r)).all()


@pytest.mark.parametrize("G,Q,L,n_docs", [(0, 3, 3, 5000), (0, 1, 1, 31),
                                          (3, 5, 3, 2000), (2, 4, 4, 40_000)])
def test_shape_class_padding_keeps_results(G, Q, L, n_docs):
    """A batch padded to its shape class reads, in its first Q rows cut
    to W words, what it reads unpadded; G = 0 is a `combine_batch` one."""
    rng = np.random.default_rng(G * 7 + Q * 3 + L)
    groups = max(G, 1)
    bm = np.stack([postings_to_bitmap_batch(
        [_random_postings(rng, L, n_docs) for _ in range(Q)], n_docs)
        for _ in range(groups)])
    packed = pack_cluster_programs(
        [_random_programs(rng, Q, L) for _ in range(groups)], L)
    x, p = pad_to_shape_class(bm, packed, n_docs)
    *_, Qc, Lc, Wc = x.shape
    assert (Qc, Lc, p.shape[-2]) == (
        shape_class(Q), shape_class(L, 4), shape_class(packed.shape[-2], 4))
    assert Wc >= 1024 and Wc & (Wc - 1) == 0
    if G:
        got, cnt = combine_cluster_ref(x, p)
        want, want_cnt = combine_cluster_ref(bm, packed)
    else:
        got, cnt = combine_batch_ref(x[0], p[0])
        want, want_cnt = combine_batch_ref(bm[0], packed[0])
    W = bm.shape[-1]
    np.testing.assert_array_equal(np.asarray(got)[..., :Q, :W], want)
    np.testing.assert_array_equal(np.asarray(cnt)[..., :Q], want_cnt)
    assert not np.asarray(got)[..., Q:, :].any()


def test_cluster_counts_match_per_shard_popcounts():
    """Fused counts must equal each shard's own combine_batch counts."""
    rng = np.random.default_rng(5)
    G, Q, L, n_docs = 3, 4, 3, 9000
    bm = np.stack([postings_to_bitmap_batch(
        [_random_postings(rng, L, n_docs) for _ in range(Q)], n_docs)
        for _ in range(G)])
    progs = [_random_programs(rng, Q, L) for _ in range(G)]
    packed = pack_cluster_programs(progs, L)
    _, cnt = combine_cluster(bm, packed, impl="pallas", n_docs=n_docs)
    for g in range(G):
        _, cnt_g = combine_batch(bm[g], pack_programs(progs[g], L),
                                 impl="pallas", n_docs=n_docs)
        assert (np.asarray(cnt[g]) == np.asarray(cnt_g)).all()
