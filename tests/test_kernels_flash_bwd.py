"""Gradient parity of the hand-written Pallas backward kernels.

Both ``flash_bwd`` and ``ca_server_bwd`` (interpret mode on CPU) must
match ``jax.grad`` through the materialized-mask oracles within
fp32-interpret tolerance, across causal/windowed/softcapped/GQA cases and
ragged ``kv_len`` server task batches — and the blockwise-XLA recompute
fallback selected via ``bwd_impl``/``REPRO_KERNEL_BWD`` must agree too.

(Deliberately hypothesis-free, unlike test_kernels_flash.py, so the bwd
parity gate runs even without the dev extra.)
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dispatch as D
from repro.core.attention import mask_fn, ref_attention
from repro.kernels.packed_flash import kernel as K
from repro.kernels.packed_flash import ops as O
from repro.kernels.packed_flash import ref as R

ATOL = 3e-4


def make_packed(key, B, S, Hq, Hkv, dh, dtype=jnp.float32, n_docs=3,
                pad_tail=0):
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, S, Hq, dh)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, dh)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, dh)).astype(dtype)
    rng = np.random.default_rng(int(ks[3][0]))
    seg = np.zeros((B, S), np.int32)
    pos = np.zeros((B, S), np.int32)
    body = S - pad_tail
    for b in range(B):
        cuts = np.sort(rng.choice(np.arange(1, body), size=n_docs - 1,
                                  replace=False))
        bounds = np.concatenate([[0], cuts, [body]])
        for d in range(n_docs):
            lo, hi = bounds[d], bounds[d + 1]
            seg[b, lo:hi] = d + 1
            pos[b, lo:hi] = np.arange(hi - lo)
    return q, k, v, jnp.asarray(seg), jnp.asarray(pos)


def make_server_batch(key, T, blk, Hq, Hkv, dh, N, seed=0, pad_last=True):
    """Ragged CA-task batch: each task a (q-block, kv-prefix-range) pair
    with random start/length; the last task is zero-length padding."""
    ks = jax.random.split(key, 4)
    rng = np.random.default_rng(seed)
    q = jax.random.normal(ks[0], (T, blk, Hq, dh)).astype(jnp.float32)
    kb = jax.random.normal(ks[1], (N, blk, Hkv, dh)).astype(jnp.float32)
    vb = jax.random.normal(ks[2], (N, blk, Hkv, dh)).astype(jnp.float32)
    kv_start = np.zeros(T, np.int32)
    kv_len = np.zeros(T, np.int32)
    q_pos = np.zeros((T, blk), np.int32)
    kv_pos = np.zeros((N, blk), np.int32)
    for n in range(N):
        kv_pos[n] = np.arange(blk)
    for t in range(T):
        ln = int(rng.integers(1, min(N, 6) + 1))
        st = int(rng.integers(0, N - ln + 1))
        kv_start[t], kv_len[t] = st, ln
        q_pos[t] = np.arange((ln - 1) * blk, ln * blk)
        for jj in range(ln):
            kv_pos[st + jj] = np.arange(jj * blk, (jj + 1) * blk)
    if pad_last and T > 1:
        kv_len[-1] = 0
        q_pos[-1] = -1
    return (q, kb, vb, jnp.asarray(kv_start), jnp.asarray(kv_len),
            jnp.asarray(q_pos), jnp.asarray(kv_pos))


def grads(loss, *args):
    return jax.grad(loss, argnums=(0, 1, 2))(*args)


def assert_grads_close(ga, gb, atol=ATOL):
    for a, b in zip(ga, gb):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=atol)


# ------------------------------------------------------------ packed flash
@pytest.mark.parametrize("causal,window,softcap,Hq,Hkv", [
    (True, 0, 0.0, 4, 2),     # GQA
    (True, 0, 0.0, 4, 4),     # MHA
    (True, 0, 0.0, 8, 1),     # MQA
    (False, 0, 0.0, 4, 2),    # bidirectional
    (True, 64, 0.0, 4, 2),    # sliding window
    (True, 0, 30.0, 4, 2),    # softcap
    (True, 128, 30.0, 6, 2),  # window + softcap, odd GQA factor
])
def test_flash_bwd_parity(causal, window, softcap, Hq, Hkv):
    q, k, v, seg, pos = make_packed(jax.random.PRNGKey(0), 2, 256, Hq,
                                    Hkv, 64)

    def loss_k(q_, k_, v_):
        out = O.packed_flash_attention(q_, k_, v_, seg, pos, seg, pos,
                                       causal, window, softcap)
        return jnp.sum(out ** 2)

    def loss_r(q_, k_, v_):
        out = ref_attention(q_, k_, v_, seg, pos, seg, pos, causal=causal,
                            window=window, softcap=softcap)
        return jnp.sum(out ** 2)

    assert_grads_close(grads(loss_k, q, k, v), grads(loss_r, q, k, v))


def test_flash_bwd_small_blocks():
    """Non-default block sizes exercise the pruning arithmetic."""
    q, k, v, seg, pos = make_packed(jax.random.PRNGKey(1), 1, 384, 4, 2,
                                    128)
    out, lse = K.flash_fwd(q, k, v, seg, pos, seg, pos, blk_q=64, blk_k=64,
                           return_lse=True)
    do = jax.random.normal(jax.random.PRNGKey(2), out.shape)
    dq, dk, dv = K.flash_bwd(q, k, v, out, lse, do, seg, pos, seg, pos,
                             blk_q=64, blk_k=64)
    f = lambda q_, k_, v_: ref_attention(q_, k_, v_, seg, pos, seg, pos)
    _, vjp = jax.vjp(f, q, k, v)
    assert_grads_close((dq, dk, dv), vjp(do))


def test_flash_bwd_padded_rows_get_zero_grad():
    """Padding tokens (segment 0) are dead rows: lse = LSE_DEAD in the
    residual and no gradient may flow through them."""
    q, k, v, seg, pos = make_packed(jax.random.PRNGKey(3), 1, 256, 4, 2,
                                    64, pad_tail=64)

    def loss_k(q_, k_, v_):
        out = O.packed_flash_attention(q_, k_, v_, seg, pos, seg, pos)
        return jnp.sum(out ** 2)

    dq, dk, dv = grads(loss_k, q, k, v)
    dead = np.asarray(seg)[0] == 0
    assert dead.any()
    np.testing.assert_array_equal(np.asarray(dq)[0, dead], 0.0)
    np.testing.assert_array_equal(np.asarray(dk)[0, dead], 0.0)
    np.testing.assert_array_equal(np.asarray(dv)[0, dead], 0.0)


def test_flash_lse_residual_matches_oracle():
    q, k, v, seg, pos = make_packed(jax.random.PRNGKey(4), 1, 256, 2, 2,
                                    64, pad_tail=32)
    _, lse = K.flash_fwd(q, k, v, seg, pos, seg, pos, return_lse=True)
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    m = mask_fn(seg, pos, seg, pos, causal=True, window=0)[:, None]
    ref_lse = np.broadcast_to(
        np.asarray(jax.nn.logsumexp(jnp.where(m, logits, -jnp.inf),
                                    axis=-1)), lse.shape)
    live = np.broadcast_to(np.asarray(m.any(-1)), lse.shape)
    np.testing.assert_allclose(np.asarray(lse)[live], ref_lse[live],
                               atol=1e-5)
    assert (np.asarray(lse)[~live] == K.LSE_DEAD).all()


def test_flash_bwd_xla_fallback_parity(monkeypatch):
    """bwd_impl="xla" (and $REPRO_KERNEL_BWD) select the blockwise
    recompute backward; both routes must match the Pallas backward."""
    q, k, v, seg, pos = make_packed(jax.random.PRNGKey(5), 1, 256, 4, 2,
                                    64)

    def loss(impl):
        def f(q_, k_, v_):
            out = O.packed_flash_attention(q_, k_, v_, seg, pos, seg, pos,
                                           True, 0, 50.0, None, impl)
            return jnp.sum(out ** 2)
        return f

    g_pallas = grads(loss("pallas"), q, k, v)
    g_xla = grads(loss("xla"), q, k, v)
    assert_grads_close(g_pallas, g_xla)

    monkeypatch.setenv("REPRO_KERNEL_BWD", "xla")
    g_env = grads(loss(None), q, k, v)
    assert_grads_close(g_env, g_xla)

    monkeypatch.setenv("REPRO_KERNEL_BWD", "bogus")
    with pytest.raises(ValueError, match="bwd impl"):
        grads(loss(None), q, k, v)


# -------------------------------------------------------------- CA server
@pytest.mark.parametrize("causal,window,softcap,Hq,Hkv", [
    (True, 0, 0.0, 4, 2),
    (True, 0, 0.0, 2, 2),
    (True, 0, 0.0, 8, 1),
    (True, 96, 0.0, 4, 2),
    (True, 0, 25.0, 4, 2),
])
def test_ca_server_bwd_parity(causal, window, softcap, Hq, Hkv):
    q, kb, vb, st, ln, qp, kp = make_server_batch(
        jax.random.PRNGKey(6), 5, 64, Hq, Hkv, 64, 7)

    def loss_k(q_, k_, v_):
        out = O.ca_server_attention(q_, k_, v_, st, ln, qp, kp, causal,
                                    window, softcap)
        return jnp.sum(out ** 2)

    def loss_r(q_, k_, v_):
        out = R.ref_ca_server_attention(q_, k_, v_, st, ln, qp, kp,
                                        causal=causal, window=window,
                                        softcap=softcap)
        return jnp.sum(out ** 2)

    assert_grads_close(grads(loss_k, q, kb, vb), grads(loss_r, q, kb, vb))


def test_ca_server_bwd_ragged_and_padded_tasks():
    """Ragged kv_len, overlapping prefix ranges, and a zero-length
    padding task: the padding task's dq must be exactly zero and kv
    blocks outside every range get zero dk/dv."""
    q, kb, vb, st, ln, qp, kp = make_server_batch(
        jax.random.PRNGKey(7), 6, 64, 4, 2, 64, 8)

    def loss_k(q_, k_, v_):
        return jnp.sum(O.ca_server_attention(q_, k_, v_, st, ln, qp,
                                             kp) ** 2)

    def loss_r(q_, k_, v_):
        return jnp.sum(R.ref_ca_server_attention(q_, k_, v_, st, ln, qp,
                                                 kp) ** 2)

    gk = grads(loss_k, q, kb, vb)
    assert_grads_close(gk, grads(loss_r, q, kb, vb))
    assert int(ln[-1]) == 0
    np.testing.assert_array_equal(np.asarray(gk[0])[-1], 0.0)
    starts, lens = np.asarray(st), np.asarray(ln)
    covered = np.zeros(kb.shape[0], bool)
    for s, n in zip(starts, lens):
        covered[s:s + n] = True
    if not covered.all():
        np.testing.assert_array_equal(np.asarray(gk[1])[~covered], 0.0)
        np.testing.assert_array_equal(np.asarray(gk[2])[~covered], 0.0)


def test_ca_server_bwd_respects_jmax():
    """jmax (the scheduler's kv-blocks-per-task bound) limits the dq
    walk exactly like the forward — results identical to jmax=N."""
    q, kb, vb, st, ln, qp, kp = make_server_batch(
        jax.random.PRNGKey(8), 4, 64, 4, 2, 64, 8, seed=3)
    jmax = int(np.asarray(ln).max())

    def loss(jm):
        def f(q_, k_, v_):
            out = O.ca_server_attention(q_, k_, v_, st, ln, qp, kp, True,
                                        0, 0.0, None, jm)
            return jnp.sum(out ** 2)
        return f

    assert_grads_close(grads(loss(jmax), q, kb, vb),
                       grads(loss(0), q, kb, vb), atol=1e-6)


def test_ca_server_bwd_xla_fallback_parity():
    q, kb, vb, st, ln, qp, kp = make_server_batch(
        jax.random.PRNGKey(9), 4, 64, 4, 2, 64, 6)

    def loss(impl):
        def f(q_, k_, v_):
            out = O.ca_server_attention(q_, k_, v_, st, ln, qp, kp, True,
                                        0, 0.0, None, 0, impl)
            return jnp.sum(out ** 2)
        return f

    assert_grads_close(grads(loss("pallas"), q, kb, vb),
                       grads(loss("xla"), q, kb, vb))


def test_ca_server_lse_residual_matches_oracle():
    q, kb, vb, st, ln, qp, kp = make_server_batch(
        jax.random.PRNGKey(10), 4, 64, 4, 2, 64, 6, seed=1)
    _, lse = K.ca_server_fwd(q, kb, vb, st, ln, qp, kp, return_lse=True)
    T, blk, hq, dh = q.shape
    N = kb.shape[0]
    scale = dh ** -0.5
    kf = jnp.repeat(kb.reshape(N * blk, -1, dh), hq // kb.shape[2], axis=1)
    logits = jnp.einsum("tqhd,khd->thqk", q, kf) * scale
    blk_idx = jnp.arange(N)
    in_rng = (blk_idx[None, :] >= st[:, None]) & \
             (blk_idx[None, :] < st[:, None] + ln[:, None])
    m = jnp.repeat(in_rng, blk, axis=1)[:, None, None, :]
    m = m & (kp.reshape(-1) >= 0)[None, None, None, :]
    m = m & (qp >= 0)[:, None, :, None]
    m = m & (qp[:, None, :, None] >= kp.reshape(-1)[None, None, None, :])
    ref_lse = np.broadcast_to(
        np.asarray(jax.nn.logsumexp(jnp.where(m, logits, -jnp.inf),
                                    axis=-1)), lse.shape)
    live = np.broadcast_to(np.asarray(m.any(-1)), lse.shape)
    np.testing.assert_allclose(np.asarray(lse)[live], ref_lse[live],
                               atol=1e-5)
    assert (np.asarray(lse)[~live] == K.LSE_DEAD).all()


def _empty_slots(batch):
    q, kb, vb, st, ln, qp, kp = batch
    ln = ln.at[1].set(0).at[3].set(0)
    return q, kb, vb, st, ln, qp.at[1].set(-1).at[3].set(-1), kp


def _uncovered_kv(batch):
    """Every range inside the first half of the kv buffer."""
    q, kb, vb, st, ln, qp, kp = batch
    n_half = kb.shape[0] // 2
    ln = jnp.minimum(ln, n_half)
    return q, kb, vb, jnp.minimum(st, n_half - ln), ln, qp, kp


@pytest.mark.parametrize("case,T,N,seed,fix,jmax,mask_kw", [
    ("ragged_padded", 6, 8, 0, None, None, {}),
    ("empty_slots", 6, 8, 1, _empty_slots, None, {}),
    ("uncovered_kv", 5, 12, 2, _uncovered_kv, None, {}),
    ("jmax_below_n", 6, 10, 3, None, "max_len", {}),
    ("sliding", 6, 8, 4, None, None, dict(window=96, sink=16)),
    ("dilated", 6, 8, 5, None, None, dict(rate=2)),
])
@pytest.mark.parametrize("list_cells", [None, 16])
def test_ca_server_pair_grid_bound_is_bitwise(case, T, N, seed, fix, jmax,
                                              mask_kw, list_cells,
                                              monkeypatch):
    """The pair grids sized by a tight ``max_pairs`` (the covered pairs
    exactly, so padding cells run too) give bitwise the outputs, lse and
    gradients of the dense-sized default: the same pairs in the same
    order, only the padding differs.  With scalar memory for 16 cells
    both lists run as several calls, which changes no bit either.  All
    agree with the blockwise XLA server, which walks no pair list."""
    if list_cells:
        monkeypatch.setattr(K, "LIST_CELLS", list_cells)
    batch = make_server_batch(jax.random.PRNGKey(20 + seed), T, 64, 4, 2,
                              64, N, seed=seed)
    if fix is not None:
        batch = fix(batch)
    q, kb, vb, st, ln, qp, kp = batch
    lens = np.asarray(ln)
    if jmax == "max_len":
        jmax = int(lens.max())
        assert jmax < N
    covered = int(np.minimum(lens, jmax or N).sum())
    tight = K._list_lengths(T, N, jmax or N, covered)
    dense = K._list_lengths(T, N, jmax or N, None)
    assert tight[0] < dense[0] and tight[1] < dense[1]
    if list_cells:
        assert K._chunking(dense[0], jmax or N)[0] > 1
        assert K._chunking(dense[1], T)[0] > 1
    if case == "empty_slots":
        assert (lens == 0).sum() == 3
    if case == "uncovered_kv":
        hit = np.zeros(N, bool)
        for s, n in zip(np.asarray(st), lens):
            hit[s:s + n] = True
        assert not hit[N // 2:].any()

    def both(fn, *args, **kw):
        return [fn(*args, jmax=jmax, max_pairs=mp, **mask_kw, **kw)
                for mp in (covered, None)]

    (out, lse), dense = both(K.ca_server_fwd, q, kb, vb, st, ln, qp, kp,
                             return_lse=True)
    for a, b in zip((out, lse), dense):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    do = jax.random.normal(jax.random.PRNGKey(seed), out.shape)
    grads_, dense = both(K.ca_server_bwd, q, kb, vb, out, lse, do, st, ln,
                         qp, kp)
    for a, b in zip(grads_, dense):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    xla = dict(jmax=jmax or N, softcap=0.0, scale=None,
               window=mask_kw.get("window", 0), sink=mask_kw.get("sink", 0),
               rate=mask_kw.get("rate", 1))
    want = D._xla_server_fwd_impl(q, kb, vb, st, ln, qp, kp, **xla)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want[0]),
                               atol=ATOL)
    live = np.asarray(want[1]) < K.LSE_DEAD / 2
    np.testing.assert_allclose(np.asarray(lse)[live],
                               np.asarray(want[1])[live], atol=ATOL)
    assert (np.asarray(lse)[~live] == K.LSE_DEAD).all()
    assert_grads_close(grads_, D._xla_server_bwd_impl(
        (q, kb, vb, st, ln, qp, kp, out, lse), do, None, **xla))
    # empty task slots and kv slots no range covers still get zeros
    np.testing.assert_array_equal(np.asarray(out)[lens == 0], 0.0)
    np.testing.assert_array_equal(np.asarray(grads_[0])[lens == 0], 0.0)
    if case == "uncovered_kv":
        np.testing.assert_array_equal(np.asarray(grads_[1])[N // 2:], 0.0)
