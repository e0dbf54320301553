"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles,
executed in interpret mode on CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import RunConfig
from repro.kernels import ops, ref
from repro.kernels.flash_attention import (_pages_per_step, cache_update,
                                           cache_update_paged,
                                           flash_attention, flash_decode,
                                           flash_decode_paged)
from repro.kernels.grouped_matmul import grouped_matmul
from repro.kernels.ssd import ssd

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                    # container fallback
    from _hypothesis_fallback import given, settings, st

KEY = jax.random.PRNGKey(0)


def _qkv(B, Sq, H, K, D, dtype, Sk=None):
    Sk = Sk if Sk is not None else Sq
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Sk, K, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Sk, K, D), jnp.float32)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype)


ATTN_SHAPES = [
    # B, S, H, K, D, block_q, block_kv
    (1, 128, 4, 4, 64, 64, 64),      # MHA
    (2, 256, 8, 2, 32, 128, 64),     # GQA 4:1
    (1, 192, 6, 3, 64, 64, 128),     # uneven block/seq (padding path)
    (2, 64, 4, 1, 128, 32, 32),      # MQA
]


@pytest.mark.parametrize("B,S,H,K,D,bq,bkv", ATTN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_causal(B, S, H, K, D, bq, bkv, dtype):
    q, k, v = _qkv(B, S, H, K, D, dtype)
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_kv=bkv,
                          interpret=True)
    exp = ref.attention_naive(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(out.astype(jnp.float32),
                               exp.astype(jnp.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [32, 100])
def test_flash_attention_local_window(window):
    q, k, v = _qkv(1, 256, 4, 2, 64, jnp.float32)
    out = flash_attention(q, k, v, causal=True, local_window=window,
                          block_q=64, block_kv=64, interpret=True)
    exp = ref.attention_naive(q, k, v, causal=True, local_window=window)
    np.testing.assert_allclose(out, exp, atol=2e-5, rtol=2e-5)


def test_flash_attention_softcap_and_scale():
    q, k, v = _qkv(2, 128, 4, 4, 64, jnp.float32)
    out = flash_attention(q, k, v, causal=True, softcap=30.0, scale=0.0625,
                          block_q=64, block_kv=64, interpret=True)
    exp = ref.attention_naive(q, k, v, causal=True, softcap=30.0,
                              scale=0.0625)
    np.testing.assert_allclose(out, exp, atol=2e-5, rtol=2e-5)


def test_flash_attention_noncausal():
    q, k, v = _qkv(1, 160, 4, 4, 32, jnp.float32)
    out = flash_attention(q, k, v, causal=False, block_q=32, block_kv=64,
                          interpret=True)
    exp = ref.attention_naive(q, k, v, causal=False)
    np.testing.assert_allclose(out, exp, atol=2e-5, rtol=2e-5)


def test_blockwise_ref_matches_naive_long():
    q, k, v = _qkv(1, 512, 2, 2, 32, jnp.float32)
    blk = ref.attention_blockwise(q, k, v, causal=True, block_kv=128)
    naive = ref.attention_naive(q, k, v, causal=True)
    np.testing.assert_allclose(blk, naive, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("lens", [[64, 128], [1, 77]])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode(lens, dtype):
    B, S, H, K, D = len(lens), 128, 8, 2, 64
    q, k, v = _qkv(B, 1, H, K, D, dtype, Sk=S)
    kv_len = jnp.array(lens, jnp.int32)
    out = flash_decode(q, k, v, kv_len, block_kv=32, interpret=True)
    exp = ref.decode_attention_ref(q, k, v, kv_len)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(out.astype(jnp.float32),
                               exp.astype(jnp.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("lens,Sq", [([5, 33, 64], 5), ([7, 12, 20], 4)])
@pytest.mark.parametrize("window", [None, 8])
def test_flash_decode_chunked_prefill(lens, Sq, window):
    """Sq > 1: a prompt chunk laid at the end of each slot's ragged kv
    window (the continuous-batching chunked-prefill attention)."""
    B, S, H, K, D = len(lens), 64, 4, 2, 32
    q, k, v = _qkv(B, Sq, H, K, D, jnp.float32, Sk=S)
    kv_len = jnp.array(lens, jnp.int32)
    out = flash_decode(q, k, v, kv_len, local_window=window, block_kv=16,
                       interpret=True)
    exp = ref.decode_attention_ref(q, k, v, kv_len, local_window=window)
    np.testing.assert_allclose(out, exp, atol=2e-5, rtol=2e-5)


@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(1, 48), min_size=1, max_size=4),
       st.integers(1, 6))
def test_flash_decode_ragged_kv_len_property(raw_lens, Sq):
    """Property: for ANY per-slot ragged kv_len vector and chunk size,
    flash_decode matches the oracle (hypothesis, or the deterministic
    fallback when hypothesis is not installed)."""
    S, H, K, D = 48, 4, 2, 16
    B = len(raw_lens)
    kv_len = jnp.array([max(Sq, l) for l in raw_lens], jnp.int32)
    q, k, v = _qkv(B, Sq, H, K, D, jnp.float32, Sk=S)
    out = flash_decode(q, k, v, kv_len, block_kv=16, interpret=True)
    exp = ref.decode_attention_ref(q, k, v, kv_len)
    np.testing.assert_allclose(out, exp, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("idx", [[0, 30, 60], [0, 61, 5], [64, 2, 7]])
def test_cache_update_per_slot_offsets(idx):
    """Per-slot-offset KV write: each row lands at its own offset; rows
    whose write would cross the cache end are dropped whole (done-slot
    semantics), identically in the kernel and the jnp reference."""
    B, S, Sn, K, D = 3, 64, 4, 2, 16
    ks = jax.random.split(KEY, 4)
    kc = jax.random.normal(ks[0], (B, S, K, D))
    vc = jax.random.normal(ks[1], (B, S, K, D))
    kn = jax.random.normal(ks[2], (B, Sn, K, D))
    vn = jax.random.normal(ks[3], (B, Sn, K, D))
    index = jnp.array(idx, jnp.int32)
    got_k, got_v = cache_update(kc, vc, kn, vn, index, interpret=True)
    exp_k, exp_v = ref.kv_cache_update_ref(kc, vc, kn, vn, index)
    np.testing.assert_array_equal(got_k, exp_k)
    np.testing.assert_array_equal(got_v, exp_v)


def _paged_pools(n_blocks, bs, K, D, B, max_blocks, seed=7):
    """Pool pair + a block table scattering each slot's logical blocks
    across the pool in interleaved (non-contiguous) order."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    k_pool = jax.random.normal(ks[0], (n_blocks, bs, K, D))
    v_pool = jax.random.normal(ks[1], (n_blocks, bs, K, D))
    perm = jax.random.permutation(ks[2], n_blocks)[:B * max_blocks]
    tables = perm.reshape(max_blocks, B).T.astype(jnp.int32)
    return k_pool, v_pool, tables


@pytest.mark.parametrize("lens,Sq", [([5, 16, 31], 1), ([9, 20, 27], 4)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_paged_matches_oracle(lens, Sq, dtype):
    """Paged decode/chunked-prefill attention over scattered pool blocks
    matches the gather-then-dense oracle for ragged kv_len."""
    B, max_blocks, bs, H, K, D = len(lens), 4, 8, 4, 2, 32
    k_pool, v_pool, tables = _paged_pools(16, bs, K, D, B, max_blocks)
    k_pool, v_pool = k_pool.astype(dtype), v_pool.astype(dtype)
    q = jax.random.normal(KEY, (B, Sq, H, D), jnp.float32).astype(dtype)
    kv_len = jnp.array(lens, jnp.int32)
    out = flash_decode_paged(q, k_pool, v_pool, kv_len, tables,
                             interpret=True)
    exp = ref.decode_attention_paged_ref(q, k_pool, v_pool, kv_len, tables)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(out.astype(jnp.float32),
                               exp.astype(jnp.float32), atol=tol, rtol=tol)


def test_flash_decode_paged_equals_dense_layout():
    """The paged kernel over a scattered pool equals the DENSE kernel
    over the gathered cache — paging is a pure layout change."""
    B, max_blocks, bs, H, K, D = 2, 4, 8, 4, 2, 32
    k_pool, v_pool, tables = _paged_pools(12, bs, K, D, B, max_blocks)
    q = jax.random.normal(KEY, (B, 1, H, D))
    kv_len = jnp.array([13, 30], jnp.int32)
    paged = flash_decode_paged(q, k_pool, v_pool, kv_len, tables,
                               interpret=True)
    dense = flash_decode(q, ref.paged_gather_ref(k_pool, tables),
                         ref.paged_gather_ref(v_pool, tables), kv_len,
                         block_kv=bs, interpret=True)
    np.testing.assert_allclose(paged, dense, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("mode", ["pallas_interpret", "reference"])
def test_paged_ops_read_and_write_one_layer_of_a_stack(mode):
    """With ``layer`` the paged ops take the whole layer stack (the
    engine's scan carries it) and touch exactly that layer."""
    B, max_blocks, bs, H, K, D, L = 3, 4, 8, 4, 2, 32, 3
    pools = [_paged_pools(16, bs, K, D, B, max_blocks, seed=s)
             for s in range(L)]
    k_stack = jnp.stack([p[0] for p in pools])
    v_stack = jnp.stack([p[1] for p in pools])
    tables = pools[0][2]
    q = jax.random.normal(KEY, (B, 2, H, D))
    kv_len = jnp.array([5, 17, 30], jnp.int32)
    layer = jnp.asarray(1, jnp.int32)
    out = ops.decode_attention_paged(q, k_stack, v_stack, kv_len, tables,
                                     layer, mode=mode)
    exp = ref.decode_attention_paged_ref(q, k_stack[1], v_stack[1], kv_len,
                                         tables)
    np.testing.assert_allclose(out, exp, atol=2e-5, rtol=2e-5)

    kn = jax.random.normal(KEY, (B, 2, K, D))
    idx = jnp.array([0, 15, 32], jnp.int32)          # last slot drops
    got_k, got_v = ops.kv_cache_update_paged(k_stack, v_stack, kn, -kn, idx,
                                             tables, layer, mode=mode)
    exp_k, exp_v = ref.kv_cache_update_paged_ref(k_stack[1], v_stack[1], kn,
                                                 -kn, idx, tables)
    np.testing.assert_array_equal(got_k[1], exp_k)
    np.testing.assert_array_equal(got_v[1], exp_v)
    for other in (0, 2):
        np.testing.assert_array_equal(got_k[other], k_stack[other])
        np.testing.assert_array_equal(got_v[other], v_stack[other])


def _live_walk_case(Sq, bs, lens_in_groups):
    """A scattered pool whose blocks are NaN except each slot's live pages
    (first row below kv_len): pages mapped for tokens to come and blocks
    no table maps are never to be read.  ``lens_in_groups(step_rows,
    logical)`` gives the slots' kv_len from the kernel's rows per grid
    step.  Returns (q, clean pools, poisoned pools, kv_len, tables)."""
    H, K, D = 32, 8, 16                 # 8 kv heads: a few pages a step
    rows = K * Sq * (H // K)
    # room for three grid steps of pages per slot
    max_blocks = 3 * _pages_per_step(rows, bs, K, 1 << 20)
    pages = _pages_per_step(rows, bs, K, max_blocks)
    lens = lens_in_groups(pages * bs, max_blocks * bs)
    B = len(lens)
    k_pool, v_pool, tables = _paged_pools(B * max_blocks + 3, bs, K, D, B,
                                          max_blocks)
    live = np.zeros(k_pool.shape[0], bool)
    for b, n in enumerate(lens):
        if n <= max_blocks * bs:
            live[np.asarray(tables)[b, :-(-n // bs)]] = True
    keep = jnp.asarray(live)[:, None, None, None]
    poisoned = tuple(jnp.where(keep, p, jnp.nan) for p in (k_pool, v_pool))
    q = jax.random.normal(KEY, (B, Sq, H, D))
    return (q, (k_pool, v_pool), poisoned, jnp.array(lens, jnp.int32),
            tables)


@pytest.mark.parametrize("bs", [8, 16])
@pytest.mark.parametrize("Sq", [1, 4])
def test_flash_decode_paged_reads_only_live_pages(Sq, bs):
    """Every block that is not one of a slot's live pages holds NaN; the
    kernel still equals the oracle on the clean pool, over ragged lengths:
    the shortest, ending on a grid step's last page, one row past it,
    several steps with a partial last page, the full table, and a done
    slot (kv_len past the table), which is zero in both."""
    q, clean, poisoned, kv_len, tables = _live_walk_case(
        Sq, bs, lambda step, logical: [Sq, step, step + 1, 2 * step + 3,
                                       logical, logical + 1])
    assert tables.shape[1] * bs > 2 * int(kv_len[1])   # several steps
    out = flash_decode_paged(q, *poisoned, kv_len, tables, interpret=True)
    exp = ref.decode_attention_paged_ref(q, *clean, kv_len, tables)
    assert not np.isnan(np.asarray(out)).any()
    np.testing.assert_allclose(out, exp, atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(out[-1], 0)


@pytest.mark.parametrize("past", [1, 8, 1000])
@pytest.mark.parametrize("impl", ["kernel", "oracle"])
def test_flash_decode_paged_done_slot_is_zero(impl, past):
    """A slot whose kv_len exceeds the table's logical length (a done
    slot, whose cache write was dropped whole) attends to nothing and
    returns zeros; the live slot beside it is unaffected."""
    q, clean, _, kv_len, tables = _live_walk_case(
        1, 8, lambda step, logical: [step + 5, logical + past])
    if impl == "kernel":
        out = flash_decode_paged(q, *clean, kv_len, tables, interpret=True)
    else:
        out = ref.decode_attention_paged_ref(q, *clean, kv_len, tables)
    np.testing.assert_array_equal(out[1], 0)
    live = ref.decode_attention_ref(
        q[:1], ref.paged_gather_ref(clean[0], tables[:1]),
        ref.paged_gather_ref(clean[1], tables[:1]), kv_len[:1])
    np.testing.assert_allclose(out[:1], live, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("softcap,window", [(20.0, None), (None, 24),
                                            (30.0, 40)])
@pytest.mark.parametrize("Sq", [1, 4])
def test_flash_decode_paged_features_through_the_walk(Sq, softcap, window):
    """Logit soft-capping and a sliding window over slots that span
    several grid steps, with every non-live block NaN: a window that
    starts inside a later step leaves whole earlier steps masked."""
    q, clean, poisoned, kv_len, tables = _live_walk_case(
        Sq, 8, lambda step, logical: [Sq + 2, step + 1, 2 * step + 5,
                                      logical])
    out = flash_decode_paged(q, *poisoned, kv_len, tables, softcap=softcap,
                             local_window=window, interpret=True)
    exp = ref.decode_attention_paged_ref(q, *clean, kv_len, tables,
                                         softcap=softcap,
                                         local_window=window)
    assert not np.isnan(np.asarray(out)).any()
    np.testing.assert_allclose(out, exp, atol=2e-5, rtol=2e-5)


def test_kernel_mode_follows_the_platform(monkeypatch):
    """No user option picks the kernels: an unset mode resolves to the
    jnp reference on CPU and to Pallas on TPU; a named mode (the training
    forward's "reference", tests' "pallas_interpret") is kept."""
    assert RunConfig().kernel_mode is None
    assert jax.default_backend() == "cpu"
    assert ops.resolve_mode(None) == "reference"
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    assert ops.resolve_mode(None) == "pallas"
    for mode in ("reference", "pallas", "pallas_interpret"):
        assert ops.resolve_mode(mode) == mode


@pytest.mark.parametrize("idx", [
    [0, 13, 28],     # block start / mid-block / tail
    [6, 30, 5],      # cross-block write (6+4 spans blocks 0 and 1)
    [32, -1, 12],    # done slot (== logical end) and negative: dropped
])
def test_cache_update_paged_per_slot_offsets(idx):
    """Paged KV write scatters each slot's rows to the (block, offset)
    its table maps them to; OOB/negative slots drop WHOLE; pool blocks
    no table row points at are untouched (in-place aliasing)."""
    B, max_blocks, bs, Sn, K, D = 3, 4, 8, 4, 2, 16
    n_blocks = 16
    k_pool, v_pool, tables = _paged_pools(n_blocks, bs, K, D, B, max_blocks)
    ks = jax.random.split(KEY, 2)
    kn = jax.random.normal(ks[0], (B, Sn, K, D))
    vn = jax.random.normal(ks[1], (B, Sn, K, D))
    index = jnp.array(idx, jnp.int32)
    got_k, got_v = cache_update_paged(k_pool, v_pool, kn, vn, index,
                                      tables, interpret=True)
    exp_k, exp_v = ref.kv_cache_update_paged_ref(k_pool, v_pool, kn, vn,
                                                 index, tables)
    np.testing.assert_array_equal(got_k, exp_k)
    np.testing.assert_array_equal(got_v, exp_v)
    unmapped = [b for b in range(n_blocks)
                if b not in set(np.asarray(tables).ravel().tolist())]
    assert unmapped                  # the scenario leaves spare blocks
    np.testing.assert_array_equal(got_k[jnp.array(unmapped)],
                                  k_pool[jnp.array(unmapped)])


@settings(max_examples=15, deadline=None)
@given(st.lists(st.integers(-1, 32), min_size=1, max_size=3),
       st.integers(1, 5))
def test_cache_update_paged_property(raw_idx, Sn):
    """Property: ANY per-slot offset vector (valid, boundary, OOB) and
    write width matches the scatter oracle exactly."""
    B, max_blocks, bs, K, D = len(raw_idx), 4, 8, 2, 8
    k_pool, v_pool, tables = _paged_pools(12, bs, K, D, B, max_blocks)
    ks = jax.random.split(KEY, 2)
    kn = jax.random.normal(ks[0], (B, Sn, K, D))
    vn = jax.random.normal(ks[1], (B, Sn, K, D))
    index = jnp.array(raw_idx, jnp.int32)
    got_k, got_v = cache_update_paged(k_pool, v_pool, kn, vn, index,
                                      tables, interpret=True)
    exp_k, exp_v = ref.kv_cache_update_paged_ref(k_pool, v_pool, kn, vn,
                                                 index, tables)
    np.testing.assert_array_equal(got_k, exp_k)
    np.testing.assert_array_equal(got_v, exp_v)


SSD_SHAPES = [
    # B, S, H, P, G, N, chunk
    (1, 64, 2, 16, 1, 16, 16),
    (2, 128, 4, 32, 2, 16, 32),
    (1, 96, 4, 16, 1, 32, 32),    # S not a multiple of 2*chunk
]


@pytest.mark.parametrize("B,S,H,P,G,N,chunk", SSD_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_kernel_vs_naive(B, S, H, P, G, N, chunk, dtype):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, G, N)).astype(dtype)
    Cm = jax.random.normal(ks[4], (B, S, G, N)).astype(dtype)
    D = jnp.ones((H,))
    y, st = ssd(x, dt, A, Bm, Cm, D, chunk=chunk, interpret=True)
    y_ref, st_ref = ref.ssd_naive(x, dt, A, Bm, Cm, D)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-3
    np.testing.assert_allclose(y.astype(jnp.float32),
                               y_ref.astype(jnp.float32), atol=tol, rtol=tol)
    np.testing.assert_allclose(st, st_ref, atol=tol, rtol=tol)


def test_ssd_with_initial_state():
    B, S, H, P, G, N = 1, 64, 2, 16, 1, 16
    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, G, N))
    Cm = jax.random.normal(ks[4], (B, S, G, N))
    h0 = jax.random.normal(ks[5], (B, H, P, N))
    y, st = ssd(x, dt, A, Bm, Cm, None, h0=h0, chunk=16, interpret=True)
    y_ref, st_ref = ref.ssd_naive(x, dt, A, Bm, Cm, None, h0=h0)
    np.testing.assert_allclose(y, y_ref, atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(st, st_ref, atol=1e-3, rtol=1e-3)


def test_ssd_chunked_ref_split_invariance():
    """Chunked == naive for any chunk size (state-passing correctness)."""
    B, S, H, P, G, N = 1, 96, 2, 8, 1, 8
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, G, N))
    Cm = jax.random.normal(ks[4], (B, S, G, N))
    y_ref, _ = ref.ssd_naive(x, dt, A, Bm, Cm)
    for chunk in (8, 16, 32, 48, 96):
        y, _ = ref.ssd_chunked(x, dt, A, Bm, Cm, chunk=chunk)
        np.testing.assert_allclose(y, y_ref, atol=1e-3, rtol=1e-3)


def test_ssd_decode_step_matches_naive_tail():
    B, S, H, P, G, N = 2, 33, 2, 8, 1, 8
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, G, N))
    Cm = jax.random.normal(ks[4], (B, S, G, N))
    y_all, _ = ref.ssd_naive(x, dt, A, Bm, Cm)
    _, st = ref.ssd_naive(x[:, :-1], dt[:, :-1], A, Bm[:, :-1], Cm[:, :-1])
    y_t, _ = ref.ssd_decode_step(st, x[:, -1], dt[:, -1], A, Bm[:, -1],
                                 Cm[:, -1])
    np.testing.assert_allclose(y_t, y_all[:, -1], atol=1e-4, rtol=1e-4)


def test_ssd_kernel_two_groups_with_initial_state():
    """Two B/C groups (zamba2-7b's ssm_ngroups) continuing from a carried
    state, as a prefill chunk after an earlier one: head h reads group
    h // (H / G)."""
    B, S, H, P, G, N = 1, 64, 4, 16, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, G, N))
    Cm = jax.random.normal(ks[4], (B, S, G, N))
    h0 = jax.random.normal(ks[5], (B, H, P, N))
    D = jnp.linspace(0.5, 1.5, H)
    y, st = ssd(x, dt, A, Bm, Cm, D, h0=h0, chunk=16, interpret=True)
    y_ref, st_ref = ref.ssd_naive(x, dt, A, Bm, Cm, D, h0=h0)
    np.testing.assert_allclose(y, y_ref, atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(st, st_ref, atol=1e-3, rtol=1e-3)
    # the groups are read: the same call with them swapped differs
    y_sw, _ = ssd(x, dt, A, Bm[:, :, ::-1], Cm[:, :, ::-1], D, h0=h0,
                  chunk=16, interpret=True)
    assert float(jnp.max(jnp.abs(y_sw - y_ref))) > 0.1


def test_ssd_decode_step_groups_match_naive_tail():
    """The one-token recurrence with two B/C groups continues the scan."""
    B, S, H, P, G, N = 2, 17, 4, 8, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(6), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, G, N))
    Cm = jax.random.normal(ks[4], (B, S, G, N))
    D = jnp.ones((H,))
    y_all, st_all = ref.ssd_naive(x, dt, A, Bm, Cm, D)
    _, st = ref.ssd_chunked(x[:, :-1], dt[:, :-1], A, Bm[:, :-1],
                            Cm[:, :-1], chunk=16)
    y_t, st_t = ref.ssd_decode_step(st, x[:, -1], dt[:, -1], A, Bm[:, -1],
                                    Cm[:, -1], D)
    np.testing.assert_allclose(y_t, y_all[:, -1], atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(st_t, st_all, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("Sq,K", [(1, 32), (8, 32), (64, 8)])
def test_paged_ops_at_head_224(Sq, K):
    """zamba2-7b's shared attention: multi-head (g = 1) at head size 224,
    not a multiple of 128 lanes, so its pool rows are 256 wide
    (``layers.paged_head_dim``).  Through the kernels: the cache write into
    one layer of a stack, then attention over the slot's pages, against
    the oracles on the unpadded rows; 32 kv heads where interpret mode
    allows it, 8 at a 64-row chunk."""
    B, max_blocks, bs, D, Dp = 2, 8, 16, 224, 256
    k_pool, v_pool, tables = _paged_pools(B * max_blocks + 1, bs, K, D, B,
                                          max_blocks)
    pad = ((0, 0),) * 4 + ((0, Dp - D),)
    k_stack = jnp.pad(jnp.stack([k_pool, -v_pool]), pad).astype(jnp.bfloat16)
    v_stack = jnp.pad(jnp.stack([v_pool, k_pool]), pad).astype(jnp.bfloat16)
    ks = jax.random.split(KEY, 3)
    kn = jax.random.normal(ks[0], (B, Sq, K, D)).astype(jnp.bfloat16)
    vn = jax.random.normal(ks[1], (B, Sq, K, D)).astype(jnp.bfloat16)
    q = jax.random.normal(ks[2], (B, Sq, K, D)).astype(jnp.bfloat16)
    idx = jnp.array([3, 100 - Sq], jnp.int32)
    layer = jnp.asarray(1, jnp.int32)
    got_k, got_v = ops.kv_cache_update_paged(k_stack, v_stack, kn, vn, idx,
                                             tables, layer,
                                             mode="pallas_interpret")
    exp_k, exp_v = ref.kv_cache_update_paged_ref(
        k_stack[1, ..., :D], v_stack[1, ..., :D], kn, vn, idx, tables)
    np.testing.assert_array_equal(got_k[1, ..., :D], exp_k)
    np.testing.assert_array_equal(got_v[1, ..., :D], exp_v)
    assert not jnp.any(got_k[..., D:]) and not jnp.any(got_v[..., D:])
    np.testing.assert_array_equal(got_k[0], k_stack[0])
    kv_len = idx + Sq
    scale = (D / 2) ** -0.5
    out = ops.decode_attention_paged(q, got_k, got_v, kv_len, tables, layer,
                                     scale=scale, mode="pallas_interpret")
    exp = ref.decode_attention_paged_ref(q, exp_k, exp_v, kv_len, tables,
                                         scale=scale)
    assert out.shape == exp.shape
    np.testing.assert_allclose(out.astype(jnp.float32),
                               exp.astype(jnp.float32), atol=2e-2,
                               rtol=2e-2)


GMM_SHAPES = [(4, 64, 32, 48, 32, 16, 16), (2, 100, 72, 130, 32, 32, 64),
              (8, 16, 128, 16, 16, 64, 16)]


@pytest.mark.parametrize("G,M,K,N,bm,bk,bn", GMM_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_matmul(G, M, K, N, bm, bk, bn, dtype):
    ks = jax.random.split(KEY, 2)
    lhs = jax.random.normal(ks[0], (G, M, K)).astype(dtype)
    rhs = jax.random.normal(ks[1], (G, K, N)).astype(dtype)
    out = grouped_matmul(lhs, rhs, block_m=bm, block_k=bk, block_n=bn,
                         interpret=True)
    exp = ref.grouped_matmul_ref(lhs, rhs)
    tol = 1e-1 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(out.astype(jnp.float32),
                               exp.astype(jnp.float32), atol=tol, rtol=tol)
