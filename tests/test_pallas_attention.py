"""Flash-attention kernel tests (interpret mode on CPU — exact math)."""

import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import distributed_training_with_pipeline_parallelism_tpu as dtpp
from distributed_training_with_pipeline_parallelism_tpu.models import transformer as tfm
from distributed_training_with_pipeline_parallelism_tpu.ops.pallas_attention import (
    _auto_block, _packed_ok, _strip_rows, causal_strips, flash_attention)


def _full(q, k, v, causal):
    dh = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (dh ** 0.5)
    if causal:
        n = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool))[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("s,block", [(64, 32), (64, 64), (96, 32)])
def test_flash_matches_dense(causal, s, block):
    b, h, dh = 2, 2, 16
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, dh))
    k = jax.random.normal(ks[1], (b, s, h, dh))
    v = jax.random.normal(ks[2], (b, s, h, dh))
    got = flash_attention(q, k, v, causal=causal, block_q=block, block_k=block)
    ref = _full(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_flash_grads_match():
    b, s, h, dh = 1, 64, 2, 16
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (b, s, h, dh))
    k = jax.random.normal(ks[1], (b, s, h, dh))
    v = jax.random.normal(ks[2], (b, s, h, dh))

    def loss_flash(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(q, k, v, causal=True,
                                               block_q=32, block_k=32)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(_full(q, k, v, True)))

    g = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-5, rtol=1e-5)


def test_model_with_flash_flag():
    cfg = dtpp.ModelConfig(dim=32, n_layers=2, n_heads=2, vocab_size=64,
                           ffn_dim=64, max_seq_len=64, arch="gpt2",
                           use_flash_attention=True)
    ref_cfg = dtpp.ModelConfig(dim=32, n_layers=2, n_heads=2, vocab_size=64,
                               ffn_dim=64, max_seq_len=64, arch="gpt2")
    params = tfm.transformer_init(jax.random.key(0), ref_cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, 64)
    a = tfm.transformer_apply(cfg, params, tokens)
    b = tfm.transformer_apply(ref_cfg, params, tokens)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window,block", [(4, 8), (8, 8), (3, 16), (20, 8)])
def test_flash_sliding_window_matches_dense(window, block):
    """Band-pruned flash vs the dense windowed mask: fwd and grads, with
    windows below/at/above the block size and crossing block boundaries."""
    b, s, h, dh = 2, 32, 2, 8
    kq, kk, kv, kg = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(kq, (b, s, h, dh))
    k = jax.random.normal(kk, (b, s, h, dh))
    v = jax.random.normal(kv, (b, s, h, dh))

    def dense(q, k, v):
        iq = jnp.arange(s)[:, None]
        ik = jnp.arange(s)[None, :]
        mask = (iq >= ik) & (iq - ik < window)
        from distributed_training_with_pipeline_parallelism_tpu.ops.attention import (
            scaled_dot_attention)
        return scaled_dot_attention(q, k, v, mask[None, None])

    got = flash_attention(q, k, v, causal=True, block_q=block, block_k=block,
                          window=window)
    want = dense(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)

    g = jax.random.normal(kg, got.shape)
    gf = jax.grad(lambda q, k, v: jnp.vdot(
        flash_attention(q, k, v, causal=True, block_q=block, block_k=block,
                        window=window), g), argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda q, k, v: jnp.vdot(dense(q, k, v), g),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_ragged_and_noncausal(causal):
    """Backward with padded rows/cols (s not a block multiple) and in the
    non-causal path: the Pallas dq/dkv kernels must mask padded keys dead
    and keep padded-query contributions zero."""
    b, s, h, dh = 2, 29, 2, 8
    ks = jax.random.split(jax.random.key(5), 4)
    q = jax.random.normal(ks[0], (b, s, h, dh))
    k = jax.random.normal(ks[1], (b, s, h, dh))
    v = jax.random.normal(ks[2], (b, s, h, dh))
    g = jax.random.normal(ks[3], q.shape)

    gf = jax.grad(lambda q, k, v: jnp.vdot(
        flash_attention(q, k, v, causal=causal, block_q=8, block_k=8), g),
        argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda q, k, v: jnp.vdot(_full(q, k, v, causal), g),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-5, rtol=2e-5)


# 'auto' on a TPU, causal, no dropout: the kernels from seq 256, whole strips
# or a ragged row alike (PR 33 moved the bound from 1024 on whole-step chip
# measurements: docs/performance.md); dense under it
AUTO_FLASH_SEQS = {128: False, 255: False, 256: True, 384: True, 512: True,
                   1000: True, 1024: True, 8192: True}


@pytest.mark.parametrize("mode", ["auto", True, False])
@pytest.mark.parametrize("dropout", [0.0, 0.1])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("seq", sorted(AUTO_FLASH_SEQS))
def test_flash_auto_resolution(monkeypatch, seq, causal, dropout, mode):
    """The rule `flash_for` resolves by, with the platform steered to a TPU:
    explicit True / False are themselves whatever the call site is; 'auto'
    is dense with dropout or without a causal mask, and else the table."""
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [types.SimpleNamespace(platform="tpu")])
    if mode is True and dropout:
        # dropout composes with dense only: forcing the kernel raises
        with pytest.raises(ValueError, match="dense"):
            dtpp.ModelConfig(arch="gpt2", dropout=dropout,
                             use_flash_attention=True)
        return
    cfg = dtpp.ModelConfig(arch="gpt2", dropout=dropout,
                           use_flash_attention=mode)
    want = (mode if mode != "auto" else
            causal and not dropout and AUTO_FLASH_SEQS[seq])
    assert cfg.flash_for(causal, seq) is want


def test_flash_auto_resolution_off_tpu():
    """On the CPU backend (the test env) 'auto' is always dense: the kernel
    would only run in (slow) interpret mode there."""
    cfg = dtpp.ModelConfig(arch="gpt2")
    assert cfg.use_flash_attention == "auto"
    for seq in AUTO_FLASH_SEQS:
        assert cfg.flash_for(True, seq) is False
    assert dtpp.ModelConfig(use_flash_attention=True).flash_for(False, 8) is True
    with pytest.raises(ValueError, match="use_flash_attention"):
        dtpp.ModelConfig(use_flash_attention="maybe")


def test_flash_window_requires_causal():
    q = jnp.zeros((1, 8, 1, 4))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, causal=False, window=4)


@pytest.mark.parametrize("s,block,window", [
    (12, 8, 5),      # the reproduced ragged corruption case
    (29, 8, None),   # ragged, plain causal
    (29, 8, 7),
    (13, None, None),  # seq smaller than the auto block: auto path clamps
])
def test_flash_ragged_seq_lengths(s, block, window):
    """Sequence lengths that do not divide the block size: padded keys must
    be dead and padded query rows sliced off."""
    b, h, dh = 2, 2, 8
    kq, kk, kv = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(kq, (b, s, h, dh))
    k = jax.random.normal(kk, (b, s, h, dh))
    v = jax.random.normal(kv, (b, s, h, dh))
    from distributed_training_with_pipeline_parallelism_tpu.ops.attention import (
        band_mask, scaled_dot_attention)
    want = scaled_dot_attention(q, k, v, band_mask(s, s, window)[None, None])
    got = flash_attention(q, k, v, causal=True, block_q=block, block_k=block,
                          window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("kwargs", [
    {"block_q": 16}, {"block_k": 16}, {"block_q": 16, "block_k": 16},
])
def test_flash_explicit_block_exceeds_seq_raises(kwargs):
    """Explicit block sizes larger than the sequence are a caller error:
    silently clamping them used to hide mis-sized launch configs. Only the
    auto path (block=None) may clamp to the sequence length."""
    s = 13
    q = jnp.zeros((1, s, 2, 8))
    with pytest.raises(ValueError, match="exceeds the sequence length"):
        flash_attention(q, q, q, causal=True, **kwargs)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_unequal_blocks_multi_padded_kblocks(causal):
    """Regression (round-4 review): with block_q > block_k the sequence
    pads to lcm(block_q, block_k), so SEVERAL trailing k blocks hold
    padded keys — the cond-gated pad mask must catch all of them, not
    just the last (ki == n_kv-1). Forward and grads vs dense."""
    b, s, h, dh = 2, 37, 2, 8     # s_pad = lcm(32, 8) = 64 -> 3 padded k blocks
    kq, kk, kv, kg = jax.random.split(jax.random.key(9), 4)
    q = jax.random.normal(kq, (b, s, h, dh))
    k = jax.random.normal(kk, (b, s, h, dh))
    v = jax.random.normal(kv, (b, s, h, dh))
    g = jax.random.normal(kg, (b, s, h, dh))
    got = flash_attention(q, k, v, causal=causal, block_q=32, block_k=8)
    want = _full(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    gf = jax.grad(lambda q, k, v: jnp.vdot(
        flash_attention(q, k, v, causal=causal, block_q=32, block_k=8), g),
        argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda q, k, v: jnp.vdot(_full(q, k, v, causal), g),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-5, rtol=2e-5)


def _assert_matches_dense(q, k, v, g, **blocks):
    """Forward and all three gradients of the causal kernels against
    ``_full``."""
    got = flash_attention(q, k, v, causal=True, **blocks)
    want = _full(q, k, v, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    gf = jax.grad(lambda q, k, v: jnp.vdot(
        flash_attention(q, k, v, causal=True, **blocks), g),
        argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda q, k, v: jnp.vdot(_full(q, k, v, True), g),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-5, rtol=2e-5)


def _qkvg(seed, shape):
    return (jax.random.normal(kk, shape)
            for kk in jax.random.split(jax.random.key(seed), 4))


@pytest.mark.parametrize("h,dh,block", [
    (4, 64, 128),   # 2 heads/slab, block tiles 128 lanes
    (2, 128, 128),  # hp=1 slab variant
    (2, 64, 256),   # single-block row (block == s): two strips of 128
])
def test_flash_packed_head_path_matches_dense(h, dh, block):
    """The head-packed (transpose-free) kernels (round 4): heads stay in
    the lane dimension as 128-lane slabs (HP = 128//head_dim per grid
    instance). Only TPU-lowerable shapes are admitted (the packed-lse
    BlockSpec needs block_q % 128 == 0 or block_q == s — review finding),
    so these configurations compile on the device, not just in interpret
    mode. Forward and grads vs dense."""
    b, s = 2, 256
    assert _packed_ok(s, h, dh, True, None, block, block)
    # sub-128 blocks must REJECT packing (Mosaic lowering would fail)
    assert not _packed_ok(s, h, dh, True, None, 64, 64)
    _assert_matches_dense(*_qkvg(11, (b, s, h, dh)), block_q=block,
                          block_k=block)


@pytest.mark.parametrize("s,h,dh,packed,t_fwd,t_bwd", [
    (256, 1, 128, True, 128, 128),   # one head a slab; 3 of 4 tiles
    (256, 3, 64, False, 128, 128),   # odd heads: the classic form
    (512, 2, 64, True, 256, 128),    # 3 of 4 and 10 of 16 tiles
    (512, 1, 64, False, 256, 128),
    (1024, 2, 64, True, 512, 128),   # the benchmark cells' row: 3 of 4, 36 of 64
])
def test_flash_causal_strips_match_dense(s, h, dh, packed, t_fwd, t_bwd):
    """One block spans the row (what ``flash_attention`` resolves to up to
    1024), so both kernels run their static-strip bodies and form no dead
    tile: same forward and gradients as dense, in the packed and in the
    classic form, at every strip height the rule returns."""
    assert bool(_packed_ok(s, h, dh, True, None, s, s)) is packed
    assert _strip_rows(s, s, s, True, None, "fwd") == t_fwd
    assert _strip_rows(s, s, s, True, None, "bwd") == t_bwd
    _assert_matches_dense(*_qkvg(13, (1, s, h, dh)))


@pytest.mark.parametrize("s,block_q,block_k,causal,window,want", [
    (1024, 1024, 1024, True, None, (512, 128)),
    (768, 768, 768, True, None, (256, 128)),
    (640, 640, 640, True, None, (128, 128)),
    (256, 256, 256, True, None, (128, 128)),
    (128, 128, 128, True, None, (None, None)),    # one strip is no cut
    (1024, 1024, 1024, True, 256, (None, None)),  # a window
    (1000, 1000, 1000, True, None, (None, None)),  # ragged: no whole strips
    (1024, 1024, 1024, False, None, (None, None)),  # nothing is dead
    (1024, 1024, 512, True, None, (None, None)),  # unequal blocks
    (2048, 512, 512, True, None, (None, None)),   # blocks: dead ones skipped
])
def test_flash_strip_rule(s, block_q, block_k, causal, window, want):
    """Strips are chosen from what a call can see — plain causal, one block
    spanning a row of >= 2 whole strips — and every other call keeps the
    kernels it had."""
    assert tuple(_strip_rows(s, block_q, block_k, causal, window, kernel)
                 for kernel in ("fwd", "bwd")) == want
    if s > 1024:  # what flash_attention resolves the blocks to by itself
        assert min(_auto_block(s), s) == block_q


def test_flash_causal_strips_count_and_log(caplog):
    assert causal_strips(1024, 256) == (10, 16)
    assert causal_strips(1024, 128) == (36, 64)
    assert causal_strips(1024, 512) == (3, 4)
    q = jax.ShapeDtypeStruct((2, 1024, 3, 64), jnp.float32)
    with caplog.at_level("INFO"):
        jax.eval_shape(lambda q: flash_attention(q, q, q, causal=True), q)
    assert ("classic kernels, 2 x 1024 x 3 x 64, blocks 1024 x 1024, "
            "fwd strips of 512 rows, 3 of 4 tiles of the causal square; "
            "bwd strips of 128 rows, 36 of 64 tiles") in caplog.text


@pytest.mark.parametrize("s,want", [
    (13, 1024), (256, 1024), (1000, 1024), (1024, 1024),  # one block a row
    (1025, 256), (1280, 256),       # 256 saves a padding block of 512
    (1281, 512), (2048, 512), (2600, 512), (7168, 512),
    (7700, 512), (8192, 512),       # 256 was forced here until PR 37
    (16384, 512),
])
def test_flash_auto_block_table(s, want):
    """The block a call resolves to from its row length alone: the whole row
    up to 1024, then 512 unless 256 pads the row by a sixth less. Rows whose
    padded length reaches 8192 take the 512 every shorter row takes (chip,
    PR 37: half the time of 256 a call, the same errors)."""
    assert _auto_block(s) == want


def _vmem_limits(shape, dv=None, window=None, **blocks):
    """MiB of scoped VMEM each kernel of a causal ``flash_attention`` call
    and its gradient asks for (None: the compiler's default), by kernel."""
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    v = jax.ShapeDtypeStruct(shape[:3] + (dv or shape[3],), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=window, **blocks).astype(
                jnp.float32).sum(), argnums=(0, 1, 2)))(q, q, v).jaxpr
    out = {}
    for name, params in _pallas_calls(jaxpr):
        mosaic = (params["compiler_params"] or {}).get("mosaic_tpu")
        limit = mosaic and mosaic.vmem_limit_bytes
        out[name.removeprefix("_flash_").replace("_kernel", "")] = (
            limit and round(limit / 2 ** 20, 1))
    return out


@pytest.mark.parametrize("shape,dv,window,block,want", [
    # test_chip_compile.py::test_flash_fwd_bwd_compiles' shapes: what
    # compiled with nothing asked before PR 37 is still asked nothing
    ((8, 1024, 16, 64), None, None, None,
     {"fwd_packed": None, "bwd_packed": None}),
    ((2, 1024, 25, 64), None, None, None, {"fwd": None, "bwd": None}),
    ((4, 1024, 12, 64), None, None, None,
     {"fwd_packed": None, "bwd_packed": None}),
    ((2, 1000, 12, 64), None, None, None,
     {"fwd_packed": None, "bwd_packed": None}),
    ((2, 1000, 25, 64), None, None, None, {"fwd": None, "bwd": None}),
    ((2, 4096, 32, 128), None, 1024, None, {"fwd": None, "bwd": None}),
    ((32, 256, 16, 64), None, None, None,
     {"fwd_packed": None, "bwd_packed": None}),
    ((16, 512, 16, 64), None, None, None,
     {"fwd_packed": None, "bwd_packed": None}),
    # 8192 rows in blocks of 512: the forward's k and v rows are 8 MiB of
    # its 16, the backward's q, do and dq rows 16: it asked before (24 MiB)
    ((2, 8192, 32, 128), None, None, None, {"fwd": None, "bwd": 28.1}),
    ((2, 8192, 32, 64), None, None, None, {"fwd": None, "bwd": 28.1}),
    ((2, 8192, 32, 128), None, 1024, None, {"fwd": None, "bwd": 28.1}),
    # latent attention: 192 takes 256 lanes, the forward's rows are 12 MiB
    # and it asks too; the backward's 28 MiB of rows and five tiles of 1 MiB.
    # The request follows the blocks: the described v5e's compiler wants
    # 30.35 MiB backward in blocks of 256 and 33.39 in blocks of 512, and a
    # factor on the rows alone (33.00 until PR 37) fitted the first only
    ((2, 8192, 32, 192), 128, None, None, {"fwd": 21.2, "bwd": 44.1}),
    ((2, 8192, 32, 192), 128, None, 256, {"fwd": 16.9, "bwd": 38.0}),
    ((2, 1024, 32, 192), 128, None, None, {"fwd": None, "bwd": None}),
])
def test_flash_vmem_requests(shape, dv, window, block, want):
    """Both classic kernels ask for the scoped VMEM their operands and
    their bodies' tiles need, where the operands pass three quarters of the
    compiler's default, and for nothing anywhere else
    (:func:`ops.pallas_attention._vmem`; the compiler's own needs are in its
    docstring and held by ``test_chip_compile.py``)."""
    assert _vmem_limits(shape, dv, window, block_q=block,
                        block_k=block) == want


def test_flash_blocks_of_512_match_dense():
    """A multi-block row in the blocks the rule picks beyond 1280 rows (and,
    since PR 37, at 8192): four blocks of 512 a row, the diagonal one peeled
    statically, forward and all three gradients against dense attention."""
    assert _auto_block(2048) == 512
    _assert_matches_dense(*_qkvg(37, (1, 2048, 1, 16)))


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, at any depth (remat,
    custom_vjp, scan and jit bodies), as (kernel function name, params)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["jaxpr"].debug_info.func_name, eqn.params
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _pallas_calls(sub)


def _kernel_calls(jaxpr):
    """Kernel function name -> how many ``pallas_call``s of it a jaxpr
    holds."""
    out = {}
    for name, _ in _pallas_calls(jaxpr):
        out[name] = out.get(name, 0) + 1
    return out


@pytest.mark.parametrize("h,dh,dv,flash,fwd,bwd", [
    (3, 64, 64, True, "_flash_fwd_kernel", "_flash_bwd_kernel"),
    (2, 64, 64, True, "_flash_fwd_kernel_packed", "_flash_bwd_kernel_packed"),
    (2, 24, 16, True, "_flash_fwd_kernel", "_flash_bwd_kernel"),
    (2, 64, 64, False, None, None),
], ids=["classic", "packed", "two-widths", "dense"])
def test_remat_layer_keeps_flash_out_and_lse(capsys, caplog, h, dh, dv, flash,
                                             fwd, bwd):
    """What ``cfg.remat_layers`` wraps a layer in
    (:func:`ops.layers.remat_layer`): the backward recomputes the layer but
    not the flash forward kernel, whose output and log-sum-exp both forward
    rules name and the policy keeps. Under the bare ``jax.checkpoint`` (all
    there was until PR 35) the gradient ran that kernel twice a layer. The
    gradients are the same numbers either way, and those of the layer
    without any checkpoint; a dense-attention layer has no such names and
    keeps what it kept. The helper says so at INFO."""
    from jax.ad_checkpoint import print_saved_residuals
    from distributed_training_with_pipeline_parallelism_tpu.ops.layers import (
        remat_layer)
    b, s, d = 2, 128, 32
    keys = jax.random.split(jax.random.key(35), 5)
    w = {"q": jax.random.normal(keys[0], (d, h * dh)) / d ** 0.5,
         "k": jax.random.normal(keys[1], (d, h * dh)) / d ** 0.5,
         "v": jax.random.normal(keys[2], (d, h * dv)) / d ** 0.5,
         "o": jax.random.normal(keys[3], (h * dv, d)) / (h * dv) ** 0.5}
    x = jax.random.normal(keys[4], (b, s, d))

    def layer(w, x):
        q, k, v = ((x @ w[n]).reshape(b, s, h, -1) for n in "qkv")
        o = (flash_attention(q, k, v, causal=True) if flash
             else _full(q, k, v, True))
        return x + o.reshape(b, s, h * dv) @ w["o"]

    with caplog.at_level("INFO"):
        forms = {"kept": remat_layer(layer, 1), "bare": jax.checkpoint(layer),
                 "none": layer}
        grads = {name: jax.grad(lambda w, x, f=f: jnp.sum(f(w, x) ** 2),
                                argnums=(0, 1)) for name, f in forms.items()}
        calls = {name: _kernel_calls(jax.make_jaxpr(g)(w, x).jaxpr)
                 for name, g in grads.items()}
    assert "remat_layers: 1 layers recomputed" in caplog.text
    assert caplog.text.count("a layer keeps flash_") == (2 if flash else 0)

    def saved(form):
        print_saved_residuals(forms[form], w, x)
        return capsys.readouterr().out.splitlines()

    # beyond w and x: the two kept (a kept value that the forward also reads
    # is listed as the no-op reduce_precision jax.checkpoint puts on it)
    extra = saved("kept")[len(saved("bare")):]
    if flash:
        assert calls["kept"] == calls["none"] == {fwd: 1, bwd: 1}
        assert calls["bare"] == {fwd: 2, bwd: 1}
        assert len(extra) == 2 and "named 'flash_lse'" in extra[1], extra
        assert extra[0].startswith(f"f32[{b * h},{s},{dv}] " if fwd.endswith(
            "kernel") else f"f32[{b},{s},{h * dv}] "), extra
    else:
        assert calls == {"kept": {}, "bare": {}, "none": {}}
        assert extra == [] and len(saved("kept")) == 5  # w's four and x
    got = {name: jax.jit(g)(w, x) for name, g in grads.items()}
    for other in ("bare", "none"):
        for a, b_ in zip(jax.tree.leaves(got["kept"]),
                         jax.tree.leaves(got[other])):
            assert jnp.array_equal(a, b_), other
