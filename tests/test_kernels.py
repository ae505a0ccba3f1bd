"""Per-kernel shape/dtype sweeps: Pallas (interpret=True on CPU) vs the
pure-jnp oracles in repro.kernels.ref, hypothesis property tests for the
bloom filter's no-false-negative invariant, and the per-backend dispatch
table (``ops.PALLAS_RUNS``) with its call tally."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.bloom import BloomFilter
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.bloom_probe import bloom_probe_pallas
from repro.kernels.hashing import fold64, hash_positions_np
from repro.kernels.knn_distance import masked_distance_pallas


# --------------------------------------------------------------------------- #
# dispatch: the backend table and the call tally
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("backend,join,interpret", [
    ("cpu", "pallas", True), ("tpu", "ref", False),
])
def test_pallas_runs_per_backend(monkeypatch, backend, join, interpret):
    monkeypatch.setattr(kops.jax, "default_backend", lambda: backend)
    for var in ("QUIP_BLOOM_IMPL", "QUIP_DIST_IMPL", "QUIP_JOIN_IMPL"):
        monkeypatch.delenv(var, raising=False)
    assert kops.default_impl() == ("pallas" if backend == "tpu" else "ref")
    assert kops.resolve_join_impl("pallas") == join
    assert kops.resolve_segment_impl("pallas") == "pallas"
    assert kops._interpret() is interpret
    assert kops.KERNEL_CALLS.key("bloom_probe", "pallas") == (
        "bloom_probe/pallas-interpret" if interpret else "bloom_probe/pallas")


def test_unknown_backend_raises(monkeypatch):
    monkeypatch.setattr(kops.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="no kernel dispatch"):
        kops.default_impl()
    with pytest.raises(RuntimeError, match="no kernel dispatch"):
        kops.resolve_bloom_impl("pallas")


def test_kernel_calls_record_the_impl_that_ran():
    before = kops.KERNEL_CALLS.snapshot()
    bits = np.zeros((1 << 14) // 32, dtype=np.uint32)
    kops.bloom_probe(bits, fold64(np.arange(5)), num_hashes=2, log2m=14,
                     impl="numpy")
    kops.bloom_probe(bits, fold64(np.arange(5)), num_hashes=2, log2m=14,
                     impl="pallas")
    after = kops.KERNEL_CALLS.snapshot()
    for key in ("bloom_probe/numpy", "bloom_probe/pallas-interpret"):
        assert after[key] == before.get(key, 0) + 1


# --------------------------------------------------------------------------- #
# bloom probe kernel
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("log2m", [14, 18, 20])
@pytest.mark.parametrize("num_hashes", [2, 4, 6])
@pytest.mark.parametrize("n", [1, 7, 1024, 5000])
def test_bloom_probe_pallas_matches_ref(log2m, num_hashes, n):
    rng = np.random.default_rng(log2m * 100 + num_hashes * 10 + n)
    bits = rng.integers(0, 2**32, (1 << log2m) // 32, dtype=np.uint32)
    keys = rng.integers(-(2**62), 2**62, n).astype(np.int64)
    folded = jnp.asarray(fold64(keys))
    bits_j = jnp.asarray(bits)
    ref = np.asarray(kref.bloom_probe_ref(bits_j, folded, num_hashes, log2m))
    pl = np.asarray(
        bloom_probe_pallas(bits_j, folded, num_hashes=num_hashes,
                           log2m=log2m, interpret=True)
    )
    np.testing.assert_array_equal(ref, pl)


@settings(max_examples=20, deadline=None)
@given(
    keys=st.lists(st.integers(-(2**40), 2**40), min_size=1, max_size=200),
    probes=st.lists(st.integers(-(2**40), 2**40), min_size=1, max_size=200),
)
def test_bloom_no_false_negatives(keys, probes):
    bf = BloomFilter("x", log2m=16, num_hashes=4)
    bf.insert(np.asarray(keys, dtype=np.int64))
    bf.mark_complete()
    out = bf.might_contain(np.asarray(keys, dtype=np.int64))
    assert out.all(), "bloom filter must never produce false negatives"
    # probes of non-inserted keys may collide but mostly miss
    out2 = bf.might_contain(np.asarray(probes, dtype=np.int64))
    inserted = set(keys)
    for p, hit in zip(probes, out2):
        if p in inserted:
            assert hit


# --------------------------------------------------------------------------- #
# masked knn distance kernel
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("nq,nr,d", [
    (1, 1, 1), (3, 5, 7), (64, 64, 32), (130, 200, 96), (128, 256, 128),
])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_masked_distance_pallas_matches_ref(nq, nr, d, dtype):
    rng = np.random.default_rng(nq * 1000 + nr + d)
    q = rng.normal(size=(nq, d)).astype(dtype)
    r = rng.normal(size=(nr, d)).astype(dtype)
    qm = (rng.random((nq, d)) > 0.35).astype(dtype)
    rm = (rng.random((nr, d)) > 0.35).astype(dtype)
    ref = np.asarray(kref.masked_distance_ref(
        jnp.asarray(q), jnp.asarray(qm), jnp.asarray(r), jnp.asarray(rm)))
    pl = np.asarray(masked_distance_pallas(
        jnp.asarray(q), jnp.asarray(qm), jnp.asarray(r), jnp.asarray(rm),
        interpret=True))
    assert ref.shape == pl.shape == (nq, nr)
    finite = np.isfinite(ref)
    np.testing.assert_array_equal(finite, np.isfinite(pl))
    np.testing.assert_allclose(ref[finite], pl[finite], rtol=2e-4, atol=2e-4)


def test_masked_knn_neighbours_agree():
    rng = np.random.default_rng(0)
    q = rng.normal(size=(32, 24)).astype(np.float32)
    r = rng.normal(size=(100, 24)).astype(np.float32)
    qm = (rng.random((32, 24)) > 0.3).astype(np.float32)
    rm = (rng.random((100, 24)) > 0.3).astype(np.float32)
    d_ref, i_ref = kref.masked_knn_ref(q, qm, r, rm, k=5)
    d_pl, i_pl = kops.masked_knn(q, qm, r, rm, k=5, impl="pallas")
    np.testing.assert_allclose(
        np.asarray(d_ref), np.asarray(d_pl), rtol=1e-3, atol=1e-3
    )
    # neighbour sets may differ only at distance ties
    same = np.asarray(i_ref) == np.asarray(i_pl)
    frac = same.mean()
    assert frac > 0.95


def test_hash_positions_consistent_numpy_vs_jnp():
    keys = np.array([0, 1, -1, 2**40, -(2**40), 12345], dtype=np.int64)
    pos_np = hash_positions_np(keys, 4, 20)
    folded = jnp.asarray(fold64(keys))
    bits = jnp.zeros((1 << 20) // 32, dtype=jnp.uint32)
    # insert via numpy positions, probe via jnp path: full agreement
    arr = np.zeros((1 << 20) // 32, dtype=np.uint32)
    np.bitwise_or.at(arr, pos_np.ravel() >> 5,
                     np.uint32(1) << (pos_np.ravel() & 31))
    hit = kref.bloom_probe_ref(jnp.asarray(arr), folded, 4, 20)
    assert np.asarray(hit).all()


# --------------------------------------------------------------------------- #
# hash-join build/probe kernel pair
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("n,card", [(1, 1), (37, 5), (300, 40), (1000, 10**9)])
def test_hash_join_build_table_invariants(n, card):
    from repro.kernels.hash_join import hash_join_build_pallas, table_log2cap

    rng = np.random.default_rng(n)
    keys = rng.integers(-card, card, n).astype(np.int64)
    folded = fold64(keys)
    log2cap = table_log2cap(n)
    slot_key, slot_idx = hash_join_build_pallas(
        jnp.asarray(folded), log2cap=log2cap, interpret=True
    )
    slot_key, slot_idx = np.asarray(slot_key), np.asarray(slot_idx)
    occupied = slot_idx >= 0
    # every build row in exactly one slot, carrying its own folded key
    assert int(occupied.sum()) == n
    assert sorted(slot_idx[occupied].tolist()) == list(range(n))
    np.testing.assert_array_equal(slot_key[occupied], folded[slot_idx[occupied]])


@pytest.mark.parametrize("nb,np_,card", [(64, 256, 7), (500, 100, 3), (200, 200, 10**9)])
def test_hash_join_probe_pallas_matches_ref(nb, np_, card):
    from repro.kernels.hash_join import (
        hash_join_build_pallas,
        hash_join_probe_pallas,
        table_log2cap,
    )

    rng = np.random.default_rng(nb * 1000 + np_)
    build = fold64(rng.integers(-card, card, nb).astype(np.int64))
    probe = fold64(rng.integers(-card, card, np_).astype(np.int64))
    max_dup = int(np.unique(build, return_counts=True)[1].max())
    c_ref, m_ref = kref.hash_join_ref(
        jnp.asarray(build), jnp.asarray(probe), max_dup
    )
    log2cap = table_log2cap(nb)
    slot_key, slot_idx = hash_join_build_pallas(
        jnp.asarray(build), log2cap=log2cap, interpret=True
    )
    c_pl, m_pl = hash_join_probe_pallas(
        slot_key, slot_idx, jnp.asarray(probe),
        log2cap=log2cap, max_dup=max_dup, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(c_ref), np.asarray(c_pl))
    np.testing.assert_array_equal(np.asarray(m_ref), np.asarray(m_pl))


# --------------------------------------------------------------------------- #
# neighbour aggregation kernels (KNN mean / categorical mode)
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("b,k,classes", [
    (1, 1, 1), (7, 3, 5), (128, 5, 130), (200, 9, 260), (130, 8, 1),
])
def test_neighbor_mode_pallas_matches_ref(b, k, classes):
    from repro.kernels.neighbor_agg import neighbor_mode_pallas

    rng = np.random.default_rng(b * 100 + k * 10 + classes)
    codes = rng.integers(0, classes, size=(b, k)).astype(np.int32)
    ref = np.asarray(kref.neighbor_mode_ref(jnp.asarray(codes), classes))
    pl = np.asarray(neighbor_mode_pallas(
        jnp.asarray(codes), num_classes=classes, interpret=True
    ))
    np.testing.assert_array_equal(ref, pl)


@pytest.mark.parametrize("b,k", [(1, 1), (5, 4), (128, 5), (300, 9)])
def test_neighbor_mean_pallas_matches_ref(b, k):
    from repro.kernels.neighbor_agg import neighbor_mean_pallas

    rng = np.random.default_rng(b + k)
    vals = rng.normal(size=(b, k)).astype(np.float32)
    ref = np.asarray(kref.neighbor_mean_ref(jnp.asarray(vals)))
    pl = np.asarray(neighbor_mean_pallas(jnp.asarray(vals), interpret=True))
    np.testing.assert_allclose(ref, pl, rtol=1e-6, atol=1e-6)


def test_neighbor_mode_tie_breaks_to_smallest_value():
    # two classes with equal count: the smaller value must win in every impl
    neigh = np.array([[9, 2, 2, 9], [5, 5, 1, 1]], dtype=np.int64)
    for impl in ("numpy", "ref", "pallas"):
        got = kops.neighbor_aggregate(neigh, categorical=True, impl=impl)
        np.testing.assert_array_equal(got, [2.0, 1.0], err_msg=f"impl={impl}")

