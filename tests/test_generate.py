"""KV-cache decoding correctness.

The ground truth is the plain full-forward model: greedy decoding with the
cache must produce exactly the tokens obtained by re-running
``transformer_apply`` on the growing sequence and taking argmax of the last
position — for both supported block families (gpt2, llama+GQA).
"""

import functools

import jax
import jax.numpy as jnp
import pytest

import distributed_training_with_pipeline_parallelism_tpu as dtpp
from distributed_training_with_pipeline_parallelism_tpu.models import transformer as tfm
from distributed_training_with_pipeline_parallelism_tpu.models.generate import (
    generate, init_cache, make_generate_fn, sample_logits, _forward_with_cache)

GPT2 = dtpp.ModelConfig(dim=32, n_layers=2, n_heads=4, vocab_size=97,
                        ffn_dim=64, max_seq_len=64, arch="gpt2")
LLAMA = dtpp.ModelConfig(dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
                         vocab_size=97, ffn_dim=64, max_seq_len=64,
                         arch="llama")


def _greedy_no_cache(cfg, params, prompt, n_new):
    toks = prompt
    apply = jax.jit(functools.partial(tfm.transformer_apply, cfg))
    for _ in range(n_new):
        logits = apply(params, toks)
        nxt = jnp.argmax(logits[:, -1], axis=-1)
        toks = jnp.concatenate([toks, nxt[:, None].astype(toks.dtype)], axis=1)
    return toks


@pytest.mark.parametrize("cfg", [GPT2, LLAMA], ids=["gpt2", "llama-gqa"])
def test_prefill_logits_match_full_forward(cfg):
    params = tfm.transformer_init(jax.random.key(0), cfg)
    prompt = jax.random.randint(jax.random.key(1), (3, 9), 0, cfg.vocab_size)
    cache = init_cache(cfg, 3, 24)
    logits, cache = _forward_with_cache(cfg, params, cache, prompt, jnp.int32(0))
    ref = tfm.transformer_apply(cfg, params, prompt)[:, -1]
    assert jnp.allclose(logits, ref, atol=1e-4), jnp.abs(logits - ref).max()


@pytest.mark.parametrize("cfg", [GPT2, LLAMA], ids=["gpt2", "llama-gqa"])
def test_greedy_cache_decode_matches_no_cache(cfg):
    params = tfm.transformer_init(jax.random.key(0), cfg)
    prompt = jax.random.randint(jax.random.key(1), (2, 5), 0, cfg.vocab_size)
    out = generate(cfg, params, prompt, 12)
    ref = _greedy_no_cache(cfg, params, prompt, 12)
    assert out.shape == (2, 17)
    assert (out == ref).all(), (out, ref)


def test_jitted_generate_fn_and_single_token():
    params = tfm.transformer_init(jax.random.key(0), GPT2)
    prompt = jax.random.randint(jax.random.key(1), (2, 4), 0, GPT2.vocab_size)
    fn = make_generate_fn(GPT2, 1)
    out = fn(params, prompt)
    assert out.shape == (2, 5)
    assert (out == _greedy_no_cache(GPT2, params, prompt, 1)).all()


def test_sampling_top_k1_equals_greedy():
    params = tfm.transformer_init(jax.random.key(0), GPT2)
    prompt = jax.random.randint(jax.random.key(1), (2, 4), 0, GPT2.vocab_size)
    greedy = generate(GPT2, params, prompt, 6)
    sampled = generate(GPT2, params, prompt, 6, key=jax.random.key(7),
                       temperature=0.8, top_k=1)
    assert (greedy == sampled).all()


def test_top_p_and_top_k_truncate_support():
    logits = jnp.log(jnp.array([[0.5, 0.3, 0.15, 0.05]]))
    keys = jax.random.split(jax.random.key(0), 200)
    draws_k = jnp.stack([sample_logits(k, logits, 1.0, top_k=2)[0] for k in keys[:100]])
    assert set(map(int, draws_k)) <= {0, 1}
    draws_p = jnp.stack([sample_logits(k, logits, 1.0, top_p=0.75)[0] for k in keys[100:]])
    assert set(map(int, draws_p)) <= {0, 1}  # 0.5+0.3 >= 0.75 closes the nucleus


@pytest.mark.parametrize("cfg", [GPT2, LLAMA], ids=["gpt2", "llama-gqa"])
def test_eos_freezes_streams_and_reports_lengths(cfg):
    """EOS-aware decode: once a row is about to consume EOS it freezes —
    the EOS token's KV never enters the cache, every later emitted token
    is forced to eos_id — and per-row lengths count through the first
    EOS. Ground truth is the no-cache greedy run truncated by hand."""
    params = tfm.transformer_init(jax.random.key(0), cfg)
    prompt = jax.random.randint(jax.random.key(1), (3, 4), 0,
                                cfg.vocab_size)
    N = 10
    ref = _greedy_no_cache(cfg, params, prompt, N)[:, 4:]
    # an eos that actually fires for at least one row: the most common
    # token in the reference streams (random-init greedy repeats a lot)
    vals, counts = jnp.unique(ref, return_counts=True)
    eos = int(vals[jnp.argmax(counts)])
    out, lengths = generate(cfg, params, prompt, N, eos_id=eos,
                            return_lengths=True)
    new = jnp.asarray(out)[:, 4:]
    for b in range(3):
        row_ref = [int(t) for t in ref[b]]
        n = row_ref.index(eos) + 1 if eos in row_ref else N
        assert int(lengths[b]) == n, (b, lengths, row_ref)
        # up to the first EOS: bit-match the unfrozen run; after: eos fill
        assert [int(t) for t in new[b][:n]] == row_ref[:n]
        assert all(int(t) == eos for t in new[b][n:])
    assert lengths.dtype == jnp.int32
    with pytest.raises(ValueError, match="eos_id"):
        generate(cfg, params, prompt, N, return_lengths=True)


def test_invalid_lengths_rejected():
    params = tfm.transformer_init(jax.random.key(0), GPT2)
    prompt = jnp.zeros((1, 60), jnp.int32)
    with pytest.raises(ValueError, match="position table"):
        generate(GPT2, params, prompt, 10)  # 70 > max_seq_len=64
    with pytest.raises(ValueError, match="max_new_tokens"):
        generate(GPT2, params, prompt[:, :4], 0)


def test_ref_decoder_generation_rejected():
    cfg = dtpp.ModelConfig(dim=16, n_layers=1, n_heads=2, vocab_size=31,
                           ffn_dim=32, arch="ref_decoder")
    params = tfm.transformer_init(jax.random.key(0), cfg)
    prompt = jnp.zeros((1, 3), jnp.int32)
    with pytest.raises(ValueError, match="non-causal"):
        generate(cfg, params, prompt, 2)


def test_generate_with_tp_sharded_params():
    """Distributed inference: generation with Megatron-sharded params on a
    (data x model) mesh produces the same tokens as unsharded generation —
    GSPMD propagates the shardings through the KV-cache decode loop."""
    import numpy as np

    from distributed_training_with_pipeline_parallelism_tpu.parallel import (
        tensor_parallel as tp)

    cfg = dtpp.ModelConfig(dim=64, n_layers=2, n_heads=4, vocab_size=128,
                           ffn_dim=128, arch="llama", n_kv_heads=2,
                           max_seq_len=64)
    params = tfm.transformer_init(jax.random.key(0), cfg)
    prompt = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab_size)
    out_ref = generate(cfg, params, prompt, max_new_tokens=10)
    mesh = tp.make_tp_mesh(n_model=4, n_data=2)
    out_tp = generate(cfg, tp.shard_params(params, cfg, mesh), prompt,
                      max_new_tokens=10)
    np.testing.assert_array_equal(np.asarray(out_ref), np.asarray(out_tp))
