"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_enable_x64", False)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


def _mk(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,Hq,Hkv,D", [
    (128, 4, 4, 64),     # MHA
    (128, 8, 2, 64),     # GQA 4:1
    (256, 4, 1, 128),    # MQA
    (96, 4, 2, 80),      # ragged block sizes + odd head dim
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 32), (False, 0)])
def test_flash_attention_sweep(S, Hq, Hkv, D, dtype, causal, window):
    from repro.kernels.flash_attention import ops, ref

    key = jax.random.PRNGKey(hash((S, Hq, Hkv, D, causal, window)) % 2**31)
    B = 2
    q = _mk(key, (B, S, Hq, D), dtype)
    k = _mk(jax.random.fold_in(key, 1), (B, S, Hkv, D), dtype)
    v = _mk(jax.random.fold_in(key, 2), (B, S, Hkv, D), dtype)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S)).astype(jnp.int32)
    o_ref = ref.attention(q, k, v, q_positions=pos, k_positions=pos,
                          causal=causal, window=window)
    o_pal = ops.flash_attention(q, k, v, q_positions=pos, k_positions=pos,
                                causal=causal, window=window, interpret=True)
    np.testing.assert_allclose(
        np.asarray(o_pal, np.float32), np.asarray(o_ref, np.float32), **_tol(dtype))


def test_flash_attention_softcap():
    from repro.kernels.flash_attention import ops, ref

    key = jax.random.PRNGKey(3)
    B, S, H, D = 1, 64, 2, 32
    q, k, v = (_mk(jax.random.fold_in(key, i), (B, S, H, D), jnp.float32)
               for i in range(3))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S)).astype(jnp.int32)
    o_ref = ref.attention(q, k, v, q_positions=pos, k_positions=pos,
                          causal=True, softcap=30.0)
    o_pal = ops.flash_attention(q, k, v, q_positions=pos, k_positions=pos,
                                causal=True, softcap=30.0, interpret=True)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref), rtol=2e-5, atol=2e-5)


def test_flash_attention_grad_matches_ref():
    from repro.kernels.flash_attention import ops, ref

    key = jax.random.PRNGKey(4)
    B, S, Hq, Hkv, D = 2, 64, 4, 2, 32
    q = _mk(key, (B, S, Hq, D), jnp.float32)
    k = _mk(jax.random.fold_in(key, 1), (B, S, Hkv, D), jnp.float32)
    v = _mk(jax.random.fold_in(key, 2), (B, S, Hkv, D), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S)).astype(jnp.int32)

    def loss_ref(q, k, v):
        return ref.attention(q, k, v, q_positions=pos, k_positions=pos,
                             causal=True).sum()

    def loss_pal(q, k, v):
        return ops.flash_attention(q, k, v, q_positions=pos, k_positions=pos,
                                   causal=True, interpret=True).sum()

    for gr, gp in zip(jax.grad(loss_ref, (0, 1, 2))(q, k, v),
                      jax.grad(loss_pal, (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("L,Hq,Hkv,D", [
    (256, 8, 2, 64), (512, 4, 4, 128), (128, 16, 1, 64), (96, 4, 2, 80),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(L, Hq, Hkv, D, dtype):
    from repro.kernels.decode_attention import ops, ref

    key = jax.random.PRNGKey(hash((L, Hq, Hkv, D)) % 2**31)
    B = 3
    q = _mk(key, (B, 1, Hq, D), dtype)
    kc = _mk(jax.random.fold_in(key, 1), (B, L, Hkv, D), dtype)
    vc = _mk(jax.random.fold_in(key, 2), (B, L, Hkv, D), dtype)
    qpos = jnp.asarray([[L // 3], [L // 2], [L - 1]], jnp.int32)
    kpos = jnp.broadcast_to(jnp.arange(L)[None], (B, L)).astype(jnp.int32)
    kpos = jnp.where(kpos <= qpos, kpos, -1)   # partially filled cache
    o_ref = ref.decode_attention(q, kc, vc, q_positions=qpos, k_positions=kpos)
    o_pal = ops.decode_attention(q, kc, vc, q_positions=qpos, k_positions=kpos,
                                 interpret=True)
    np.testing.assert_allclose(
        np.asarray(o_pal, np.float32), np.asarray(o_ref, np.float32), **_tol(dtype))


def test_decode_attention_ring_buffer_window():
    """Ring-buffer layout: positions wrap modulo window."""
    from repro.kernels.decode_attention import ops, ref

    key = jax.random.PRNGKey(7)
    B, L, Hkv, Hq, D = 2, 64, 2, 4, 32
    q = _mk(key, (B, 1, Hq, D), jnp.float32)
    kc = _mk(jax.random.fold_in(key, 1), (B, L, Hkv, D), jnp.float32)
    vc = _mk(jax.random.fold_in(key, 2), (B, L, Hkv, D), jnp.float32)
    cur = 150   # decoded beyond the ring: slots hold positions 87..150
    slots = np.arange(L)
    pos_at_slot = cur - ((cur - slots) % L)
    kpos = jnp.broadcast_to(jnp.asarray(pos_at_slot)[None], (B, L)).astype(jnp.int32)
    qpos = jnp.full((B, 1), cur, jnp.int32)
    o_ref = ref.decode_attention(q, kc, vc, q_positions=qpos, k_positions=kpos,
                                 window=L)
    o_pal = ops.decode_attention(q, kc, vc, q_positions=qpos, k_positions=kpos,
                                 window=L, interpret=True)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


def _shuffled_tables(B, nb, N, bs, q_lens):
    """Block tables over shuffled non-contiguous physical blocks; block 0
    (garbage) is never handed out."""
    perm = np.random.default_rng(0).permutation(np.arange(1, N))
    tables = np.zeros((B, nb), np.int32)
    ptr = 0
    for b, p in enumerate(q_lens):
        need = (p + 1 + bs - 1) // bs
        tables[b, :need] = perm[ptr:ptr + need]
        ptr += need
    return jnp.asarray(tables)


@pytest.mark.parametrize("window", [0, 24])
def test_paged_decode_attention_matches_oracle(window):
    """Block-pool kernel (scalar-prefetched block tables) vs the gather
    oracle, over shuffled non-contiguous physical blocks of a one-layer
    lane-dense pool (1, N, bs, Hkv*D)."""
    from repro.kernels.decode_attention import ops, ref

    key = jax.random.PRNGKey(11)
    B, Hq, Hkv, D, bs, nb, N = 3, 8, 2, 64, 16, 4, 14
    q = _mk(key, (B, 1, Hq, D), jnp.float32)
    kp = _mk(jax.random.fold_in(key, 1), (1, N, bs, Hkv * D), jnp.float32)
    vp = _mk(jax.random.fold_in(key, 2), (1, N, bs, Hkv * D), jnp.float32)
    q_lens = [5, 17, 63]
    tables = _shuffled_tables(B, nb, N, bs, q_lens)
    qpos = jnp.asarray([[p] for p in q_lens], jnp.int32)
    o_ref = ref.paged_decode_attention(
        q, kp, vp, block_tables=tables, q_positions=qpos, layer=0,
        window=window)
    o_pal = ops.paged_decode_attention(
        q, kp, vp, block_tables=tables, q_positions=qpos, layer=0,
        window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("Hq,Hkv,window", [
    (4, 4, 0),     # MHA
    (8, 2, 0),     # GQA 4:1
    (8, 2, 24),    # GQA under a sliding window
])
def test_paged_decode_attention_stacked_pool_layer(Hq, Hkv, window):
    """The kernel reads one layer of a pool stacked over layers, lane-dense
    (layers, N, bs, Hkv*D), picked by its scalar-prefetched ``layer``: at a
    layer other than 0 it matches the reference and a plain attention over
    that layer's own K/V, gathered by hand from the (N, bs, Hkv, D) data."""
    from repro.kernels.decode_attention import ops, ref

    key = jax.random.PRNGKey(17)
    B, D, bs, nb, N, layers, layer = 3, 32, 16, 4, 14, 3, 2
    q = _mk(key, (B, 1, Hq, D), jnp.float32)
    k4 = _mk(jax.random.fold_in(key, 1), (layers, N, bs, Hkv, D), jnp.float32)
    v4 = _mk(jax.random.fold_in(key, 2), (layers, N, bs, Hkv, D), jnp.float32)
    kp = k4.reshape(layers, N, bs, Hkv * D)
    vp = v4.reshape(layers, N, bs, Hkv * D)
    q_lens = [5, 17, 63]
    tables = _shuffled_tables(B, nb, N, bs, q_lens)
    qpos = jnp.asarray([[p] for p in q_lens], jnp.int32)
    L = nb * bs
    o_plain = ref.decode_attention(
        q, k4[layer][tables].reshape(B, L, Hkv, D),
        v4[layer][tables].reshape(B, L, Hkv, D), q_positions=qpos,
        k_positions=jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L)),
        window=window)
    o_ref = ref.paged_decode_attention(
        q, kp, vp, block_tables=tables, q_positions=qpos,
        layer=jnp.int32(layer), window=window)
    o_pal = ops.paged_decode_attention(
        q, kp, vp, block_tables=tables, q_positions=qpos,
        layer=jnp.int32(layer), window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(o_ref), np.asarray(o_plain),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_plain),
                               rtol=2e-5, atol=2e-5)


def test_paged_decode_attention_matches_contiguous():
    """Identical KV served paged vs contiguous gives identical outputs:
    garbage-block table entries and unwritten block tails are masked."""
    from repro.kernels.decode_attention import ref

    key = jax.random.PRNGKey(3)
    B, L, Hq, Hkv, D, bs = 2, 64, 4, 2, 32, 16
    q = _mk(key, (B, 1, Hq, D), jnp.float32)
    kc = _mk(jax.random.fold_in(key, 1), (B, L, Hkv, D), jnp.float32)
    vc = _mk(jax.random.fold_in(key, 2), (B, L, Hkv, D), jnp.float32)
    qpos = jnp.asarray([[20], [47]], jnp.int32)
    kpos = jnp.broadcast_to(jnp.arange(L)[None], (B, L)).astype(jnp.int32)
    o_contig = ref.decode_attention(q, kc, vc, q_positions=qpos,
                                    k_positions=kpos)
    # pool: block 0 garbage, rows interleaved — row b block j at 1 + j*B + b;
    # one layer, lane-dense (1, N, bs, Hkv*D)
    nb = L // bs
    kp = jnp.concatenate([jnp.zeros((1, bs, Hkv, D))] + [
        kc[b, j * bs:(j + 1) * bs][None] for j in range(nb) for b in range(B)
    ]).reshape(1, -1, bs, Hkv * D)
    vp = jnp.concatenate([jnp.zeros((1, bs, Hkv, D))] + [
        vc[b, j * bs:(j + 1) * bs][None] for j in range(nb) for b in range(B)
    ]).reshape(1, -1, bs, Hkv * D)
    tables = jnp.asarray(
        [[1 + j * B + b for j in range(nb)] for b in range(B)], jnp.int32)
    o_paged = ref.paged_decode_attention(
        q, kp.astype(kc.dtype), vp.astype(vc.dtype), block_tables=tables,
        q_positions=qpos, layer=0)
    np.testing.assert_allclose(np.asarray(o_paged), np.asarray(o_contig),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# linear recurrence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,W", [(256, 128), (512, 160), (64, 512), (100, 96)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_linear_recurrence_sweep(S, W, dtype):
    from repro.kernels.linear_recurrence import ops, ref

    key = jax.random.PRNGKey(hash((S, W)) % 2**31)
    B = 2
    a = jax.nn.sigmoid(_mk(key, (B, S, W), dtype)) * 0.2 + 0.8
    b = _mk(jax.random.fold_in(key, 1), (B, S, W), dtype) * 0.1
    h0 = _mk(jax.random.fold_in(key, 2), (B, W), dtype)
    h_ref = ref.linear_recurrence(a, b, h0)
    h_pal = ops.linear_recurrence(a, b, h0, interpret=True)
    np.testing.assert_allclose(np.asarray(h_pal), np.asarray(h_ref),
                               rtol=1e-4, atol=1e-5)


def test_linear_recurrence_matches_sequential():
    """Oracle-of-the-oracle: associative scan == naive python loop."""
    from repro.kernels.linear_recurrence import ref

    rng = np.random.default_rng(0)
    B, S, W = 1, 37, 8
    a = rng.uniform(0.8, 1.0, (B, S, W)).astype(np.float32)
    b = rng.standard_normal((B, S, W)).astype(np.float32) * 0.1
    h0 = rng.standard_normal((B, W)).astype(np.float32)
    h = h0.copy()
    expected = []
    for t in range(S):
        h = a[:, t] * h + b[:, t]
        expected.append(h.copy())
    expected = np.stack(expected, axis=1)
    got = np.asarray(ref.linear_recurrence(jnp.asarray(a), jnp.asarray(b),
                                           jnp.asarray(h0)))
    np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-6)


def test_linear_recurrence_grad():
    from repro.kernels.linear_recurrence import ops, ref

    key = jax.random.PRNGKey(9)
    B, S, W = 1, 64, 32
    a = jax.nn.sigmoid(_mk(key, (B, S, W), jnp.float32)) * 0.2 + 0.8
    b = _mk(jax.random.fold_in(key, 1), (B, S, W), jnp.float32) * 0.1
    h0 = jnp.zeros((B, W))
    g_ref = jax.grad(lambda b_: ref.linear_recurrence(a, b_, h0).sum())(b)
    g_pal = jax.grad(lambda b_: ops.linear_recurrence(a, b_, h0,
                                                      interpret=True).sum())(b)
    np.testing.assert_allclose(np.asarray(g_pal), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(8, 128), (2, 33, 384), (1, 7, 5, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    from repro.kernels.rmsnorm import ops, ref

    key = jax.random.PRNGKey(hash(shape) % 2**31)
    x = _mk(key, shape, dtype)
    s = _mk(jax.random.fold_in(key, 1), (shape[-1],), jnp.float32) * 0.1
    o_ref = ref.rmsnorm(x, s)
    o_pal = ops.rmsnorm(x, s, interpret=True)
    np.testing.assert_allclose(
        np.asarray(o_pal, np.float32), np.asarray(o_ref, np.float32), **_tol(dtype))


def test_rmsnorm_grad():
    from repro.kernels.rmsnorm import ops, ref

    key = jax.random.PRNGKey(11)
    x = _mk(key, (4, 64), jnp.float32)
    s = _mk(jax.random.fold_in(key, 1), (64,), jnp.float32) * 0.1
    g_ref = jax.grad(lambda x_: ref.rmsnorm(x_, s).sum())(x)
    g_pal = jax.grad(lambda x_: ops.rmsnorm(x_, s, interpret=True).sum())(x)
    np.testing.assert_allclose(np.asarray(g_pal), np.asarray(g_ref),
                               rtol=1e-5, atol=1e-6)
