"""Configuration dataclasses for models, meshes, schedules, and runs.

Reference parity: the reference keeps hyperparameters in a tiny ``ModelArgs``
dataclass (``LLMsDistributedTrainingHelper.py:23-28``: dim=768, n_layers=8,
n_heads=8, vocab_size=10000) and hard-codes run constants (batch 32, seq 128,
4 microbatches) inline. Here every knob is an explicit dataclass so the sweep
driver stays declarative.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Decoder-only transformer LM hyperparameters.

    Defaults mirror the reference's ``ModelArgs`` plus the implicit defaults it
    inherits from ``nn.TransformerDecoderLayer`` (ffn_dim=2048, post-LN,
    relu activation, no causal mask, no positional encoding —
    ``LLMsDistributedTrainingHelper.py:31-55`` never passes masks and never adds
    position embeddings).

    ``arch`` selects the block family:
      - "ref_decoder": reference-parity block — post-LN, self-attn + cross-attn
        where memory == the block's own input (``layer(h, h)``), relu MLP.
      - "gpt2": pre-LN, causal self-attn, gelu MLP, learned position embeddings.
      - "llama": pre-RMSNorm, causal self-attn with RoPE, SwiGLU MLP, no biases,
        tied-free output head.
      - "nemotron_h": pre-RMSNorm residual layers of ONE mixer each, chosen
        per layer by ``hybrid_override_pattern`` (``M`` Mamba-2, ``C`` a
        gated short convolution, ``*`` causal GQA attention — without
        positions, or with RoPE and per-head q/k norms —, ``L`` latent
        attention with RoPE, ``-`` a dense MLP, ``E`` routed experts, with a
        shared expert or without), as
        one expert-parallel rank holds it (``experts_held``). One pipeline
        stage without tensor/sequence/fsdp axes; training and eval only
        (``models/nemotron_h.py``).
    """

    dim: int = 768
    n_layers: int = 8
    n_heads: int = 8
    vocab_size: int = 10000
    ffn_dim: int = 2048
    max_seq_len: int = 2048
    arch: str = "ref_decoder"
    dropout: float = 0.0  # train-mode dropout rate. The reference implicitly
    # trains with torch's default 0.1 (nn.TransformerDecoderLayer); we default
    # to 0.0 for determinism (it never asserts loss values — only throughput).
    # Active only when an rng is passed to the apply/loss/pipeline functions
    # (train mode); calls without an rng always run deterministically.
    dtype: str = "float32"
    # Mixed-precision master weights: store parameters in this dtype while
    # computing in ``dtype``. None = same as ``dtype`` (no mixing). The
    # standard TPU recipe is dtype="bfloat16", param_dtype="float32": MXU
    # matmuls run bf16, but weights, gradient accumulation, and optimizer
    # moments stay fp32 (the cast sits inside autodiff, so grads come back
    # fp32 automatically).
    param_dtype: Optional[str] = None
    # Tie the output head to the token embedding (GPT-2 upstream,
    # Llama-3.2-class): the head has no "out" matrix; logits are
    # ``norm(h) @ embed.tok.T`` and the embedding receives gradient from
    # both its lookup and the head matmul. The reference's Linear head is
    # untied (SURVEY.md C2), so False is the parity default.
    tie_embeddings: bool = False
    # Ignore-index loss masking: target positions equal to this id contribute
    # nothing to the loss, and the mean divides by the GLOBAL valid-token
    # count (torch CrossEntropyLoss(ignore_index=...) semantics) — for
    # right-padded batches of ragged sequences. None = every position counts
    # (the reference's regime).
    pad_token_id: Optional[int] = None
    # Attention kernel routing: True forces the Pallas flash kernel, False
    # forces dense XLA softmax-matmuls, "auto" (default) picks flash where
    # the whole train step measured faster with it on the v5e — causal
    # attention at seq >= 256 with no attention-prob dropout (the rule and
    # the measurements that set it: :meth:`flash_for`) — and dense
    # everywhere else (shorter sequences, non-causal ref_decoder, CPU CI).
    use_flash_attention: Union[bool, str] = "auto"
    use_fused_xent: bool = False  # route the loss through the Pallas fused-CE kernel
    # jax.checkpoint each layer, trading FLOPs for HBM: the backward recomputes
    # a layer from its input, all but the flash kernels' output and
    # log-sum-exp, which are kept (ops/layers.py:remat_layer), and in the
    # patterned stack the named product outputs the chip has room for,
    # chosen where the step is traced (models/nemotron_h.py:kept_names; no
    # field sets the budget: it is the chip's memory less what the step holds)
    remat_layers: bool = False
    # Unroll the per-layer scan into straight-line code: XLA fuses across
    # layers and backward residuals avoid the scan-boundary HBM round-trip
    # (measured +5-12% train-step throughput on one v5e chip at GPT-2
    # scale, docs/performance.md). Costs compile time on deep models.
    unroll_layers: bool = False
    # Llama-only knobs.
    n_kv_heads: Optional[int] = None
    rope_theta: float = 10000.0
    # Llama-3.1 rope scaling: (factor, low_freq_factor, high_freq_factor,
    # original_max_position_embeddings), or None for plain RoPE.
    rope_scaling: Optional[Tuple[float, float, float, int]] = None
    rms_eps: float = 1e-5
    # Mistral-style sliding-window attention: each position attends to at
    # most this many preceding positions (None = full causal). llama arch.
    sliding_window: Optional[int] = None
    # Qwen2-style attention biases: q/k/v projections carry biases while the
    # output projection stays bias-free. llama arch only.
    attention_qkv_bias: bool = False
    # Gemma-family knobs (llama arch only):
    # - head_dim_override: decouple per-head width from dim/n_heads
    #   (Gemma: 256 regardless of dim); None = dim // n_heads.
    # - mlp_act: the gated MLP's gate activation — "silu" (Llama SwiGLU) or
    #   "gelu" (Gemma GeGLU, tanh approximation).
    # - embed_scale: multiply embedding OUTPUTS by sqrt(dim) (the tied head
    #   keeps the unscaled table, so this cannot fold into the weights).
    #   Unlike the other two knobs, also allowed on arch='gpt2' so the MoE
    #   LM (gpt2-style blocks) can use Gemma-style scaled embeddings.
    # Gemma's (1 + w) RMSNorm parametrization needs no knob: the +1 is
    # folded into the stored scale at HF import/export (models/hf.py).
    head_dim_override: Optional[int] = None
    mlp_act: str = "silu"
    embed_scale: bool = False
    # TP comm/compute overlap for the manual-SPMD MLP under a 'model' axis:
    # "none" (default) keeps the unfused Megatron block bitwise unchanged;
    # "ring" routes the MLP boundary through the collective-matmul forms
    # (ops.collectives.all_gather_matmul / matmul_reduce_scatter), which
    # overlap the TP all-gather with the up-projection and the
    # reduce-scatter with the down-projection (requires seq divisible by
    # the model-axis size); "auto" picks ring on TPU where the shapes
    # divide and falls back to the unfused path on the CPU proxy
    # (parallel.tensor_parallel.resolve_tp_overlap).
    tp_overlap: str = "none"
    # nemotron_h only, under the names of the source's ``config.json``
    # (hidden_size, num_attention_heads, num_key_value_heads, head_dim,
    # layer_norm_epsilon and vocab_size are ``dim``, ``n_heads``,
    # ``n_kv_heads``, ``head_dim_override``, ``rms_eps`` and ``vocab_size``).
    # One letter a layer; ``n_layers`` is its length.
    hybrid_override_pattern: Optional[str] = None
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    n_routed_experts: int = 128  # the router's width, whatever is held here
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712  # 0: no shared expert
    routed_scaling_factor: float = 2.5
    # The ids (of ``n_routed_experts``) whose weights this rank holds; None =
    # all. A token's weights are normalised over ALL its chosen experts and
    # only the held ones' outputs are computed (``ops/experts.py``).
    experts_held: Optional[Tuple[int, ...]] = None
    # The form of the ``-`` and ``E`` layers' MLPs: "relu2" (two matrices,
    # Nemotron-H's ``mlp_hidden_act``) or "silu" (gated, three matrices: the
    # ``hidden_act`` of DeepSeek-V3-style families). ``-`` is ``ffn_dim`` wide.
    mlp_hidden_act: str = "relu2"
    # The ``L`` layers (multi-head latent attention), under the source's
    # names; ``n_heads`` heads, RoPE at ``rope_theta`` in interleaved pairs
    # on the ``qk_rope_head_dim`` columns, no scaling.
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # The ``C`` layers (LFM2's gated short convolution), under the source's
    # names: taps a channel, and whether the convolution and both
    # projections carry a bias.
    conv_L_cache: int = 3
    conv_bias: bool = False
    # The ``*`` layers as LFM2 has them: an RMSNorm (``rms_eps``) over the
    # columns of every query head and every key head (the source's modules
    # ``q_layernorm`` / ``k_layernorm``), then RoPE in split halves at
    # ``rope_theta``, no scaling. Both off: Nemotron-H's attention, which
    # has neither.
    qk_layernorm: bool = False
    attn_rope: bool = False
    # What guards the sum a token's routing weights are normalised by
    # (DeepSeek-V3-style sources 1e-20, LFM2's code 1e-6).
    router_norm_eps: float = 1e-20

    def __post_init__(self):
        if self.dim % self.n_heads != 0:
            raise ValueError(f"dim={self.dim} must be divisible by n_heads={self.n_heads}")
        if self.n_kv_heads is not None and self.n_heads % self.n_kv_heads != 0:
            raise ValueError(f"n_heads={self.n_heads} must be divisible by n_kv_heads={self.n_kv_heads}")
        if self.arch not in ("ref_decoder", "gpt2", "llama", "nemotron_h"):
            raise ValueError(f"unknown arch {self.arch!r}")
        if self.arch == "nemotron_h":
            self._check_nemotron_h()
        elif self.hybrid_override_pattern is not None:
            raise ValueError("hybrid_override_pattern requires "
                             "arch='nemotron_h'")
        if self.attention_qkv_bias and self.arch != "llama":
            raise ValueError("attention_qkv_bias requires arch='llama' "
                             "(Qwen2-family blocks; gpt2/ref biases are "
                             "always on)")
        if self.mlp_act not in ("silu", "gelu"):
            raise ValueError(f"mlp_act={self.mlp_act!r} must be 'silu' or "
                             f"'gelu'")
        if self.mlp_act != "silu" and self.arch != "llama":
            raise ValueError("mlp_act is a Gemma-family knob on arch='llama' "
                             "blocks")
        if (self.head_dim_override is not None
                and self.arch not in ("llama", "nemotron_h")):
            raise ValueError("head_dim_override is a knob of arch='llama' "
                             "(Gemma-family) and arch='nemotron_h' blocks")
        if self.embed_scale and self.arch == "ref_decoder":
            raise ValueError("embed_scale applies to gpt2/llama blocks "
                             "(Gemma-style scaled embeddings; gpt2 is "
                             "allowed so the MoE LM — gpt2-style blocks — "
                             "can use it)")
        if self.head_dim_override is not None and self.head_dim_override < 1:
            raise ValueError(f"head_dim_override={self.head_dim_override}")
        if self.sliding_window is not None:
            if self.arch != "llama":
                raise ValueError("sliding_window requires arch='llama' "
                                 "(Mistral-family blocks)")
            if self.sliding_window < 1:
                raise ValueError(f"sliding_window={self.sliding_window} must "
                                 f"be >= 1")
        if not 0.0 <= self.dropout < 1.0:
            raise ValueError(f"dropout={self.dropout} must be in [0, 1)")
        if self.tp_overlap not in ("none", "ring", "auto"):
            raise ValueError(f"tp_overlap={self.tp_overlap!r} must be "
                             f"'none', 'ring', or 'auto'")
        if self.use_flash_attention not in (True, False, "auto"):
            raise ValueError(
                f"use_flash_attention={self.use_flash_attention!r} must be "
                f"True, False, or 'auto'")
        if self.dropout > 0.0 and self.use_flash_attention is True:
            raise ValueError(
                "dropout composes with the dense XLA attention path only: "
                "the Pallas flash kernel does not implement attention-prob "
                "dropout (torch applies dropout to attention weights, so "
                "silently skipping it would change train-mode semantics; "
                "'auto' resolves to the dense path under dropout)")

    def _check_nemotron_h(self) -> None:
        pattern = self.hybrid_override_pattern
        if not pattern:
            raise ValueError("arch='nemotron_h' needs hybrid_override_pattern "
                             "(one of 'M', 'C', '*', 'L', '-', 'E' a layer)")
        unknown = sorted(set(pattern) - set("MC*L-E"))
        if unknown:
            raise ValueError(
                f"hybrid_override_pattern {pattern!r}: unknown layer kind(s) "
                f"{unknown}; 'M' is Mamba-2, 'C' a gated short convolution, "
                "'*' attention, 'L' latent attention, '-' a dense MLP, 'E' "
                "experts")
        if self.conv_L_cache < 1:
            raise ValueError(f"conv_L_cache={self.conv_L_cache} must be >= 1")
        if self.attn_rope and self.head_dim % 2:
            raise ValueError(f"attn_rope: head_dim={self.head_dim} must be "
                             "even: RoPE turns pairs")
        if self.moe_shared_expert_intermediate_size < 0:
            raise ValueError("moe_shared_expert_intermediate_size="
                             f"{self.moe_shared_expert_intermediate_size} "
                             "must be >= 0 (0: no shared expert)")
        if self.mlp_hidden_act not in ("relu2", "silu"):
            raise ValueError(f"mlp_hidden_act={self.mlp_hidden_act!r} must "
                             "be 'relu2' or 'silu' (gated)")
        if self.qk_rope_head_dim % 2:
            raise ValueError(f"qk_rope_head_dim={self.qk_rope_head_dim} must "
                             "be even: RoPE turns pairs")
        if self.n_layers != len(pattern):
            raise ValueError(f"n_layers={self.n_layers} is not the length of "
                             f"hybrid_override_pattern {pattern!r}")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError(f"mamba_num_heads={self.mamba_num_heads} must be "
                             f"divisible by n_groups={self.n_groups}")
        held = self.held_experts
        if (len(set(held)) != len(held) or not held
                or min(held) < 0 or max(held) >= self.n_routed_experts):
            raise ValueError(f"experts_held={held} must be distinct ids of "
                             f"the {self.n_routed_experts} routed experts")
        if not 1 <= self.num_experts_per_tok <= self.n_routed_experts:
            raise ValueError(f"num_experts_per_tok={self.num_experts_per_tok}")
        if self.tie_embeddings or self.dropout or self.pad_token_id is not None:
            raise NotImplementedError(
                "arch='nemotron_h' has an untied head and no dropout or pad "
                "masking: tie_embeddings, dropout and pad_token_id are not "
                "written for its layers")

    @property
    def held_experts(self) -> Tuple[int, ...]:
        """The routed experts' ids this rank holds (nemotron_h)."""
        if self.experts_held is None:
            return tuple(range(self.n_routed_experts))
        return tuple(self.experts_held)

    @property
    def causal(self) -> bool:
        return self.arch != "ref_decoder"

    def flash_for(self, causal: bool, seq_len: int) -> bool:
        """Resolve ``use_flash_attention`` for one attention call site.
        'auto' = the Pallas kernels for causal attention without
        attention-prob dropout at ``seq_len >= 256`` on a TPU, dense
        elsewhere (other backends would run the kernel in slow interpret
        mode). A function of what the call site passes, nothing to tune.

        The bound is where the WHOLE train step measured faster with the
        kernels on one v5e (PR 33; gpt2-medium, bf16 over fp32 masters,
        AdamW, tokens/s dense -> flash, one seed a pair, ``chiprun_out/pr33``;
        docs/performance.md has the table): 32 x 256 41 525 -> 51 457
        (+23.9%, seeds 2147485301 and 2500000133: dense stores [b, h, s, s]
        scores in every layer, and XLA, short of HBM, then runs the head's
        matmul three times); 8 x 256 +6.4% by the step's median; a ragged
        8 x 300 33 877 -> 37 176 (+9.7%); 16 x 512 and 8 x 1000 run only
        with the kernels (the compiler refuses dense: 18.7 and 19.6 of
        15.75 GB); 1024 and up as ever. Under 256 dense stays: 64 x 128 and
        8 x 128 read +0.9% and +0.8% with the kernels (level), 8 x 192
        +4.3% and 40 x 192 +14.7% — a training step would gain from 192 —
        but a forward-only caller (the pipelined decode's whole-prompt
        prefill, ``models/generate.py:_layer_step``) reads the same rule and
        cannot say that no backward follows: whole-model prefill of 8
        prompts of 256 is 9.4% SLOWER with the kernel (300: 2.6%, 512: 1.0%;
        one prompt is 2-7% faster at every length, 8 x 700 17%, 8 x 900
        24%), and nothing forward-only is measured under 256. Move the
        bound only on a chip measurement of the whole step, and write it
        here."""
        if self.use_flash_attention is True:
            return True
        if self.use_flash_attention == "auto":
            if self.dropout > 0.0 or not causal or seq_len < 256:
                return False
            import jax
            return jax.devices()[0].platform == "tpu"
        return False

    @property
    def storage_dtype(self) -> str:
        """The dtype parameters are stored in (param_dtype, else dtype)."""
        return self.param_dtype or self.dtype

    @property
    def mixed_precision(self) -> bool:
        return self.storage_dtype != self.dtype

    @property
    def head_dim(self) -> int:
        if self.head_dim_override is not None:
            return self.head_dim_override
        assert self.dim % self.n_heads == 0
        return self.dim // self.n_heads


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Device-mesh shape. axis order is ('data', 'pipe')."""

    n_pipe: int = 2
    n_data: int = 1

    @property
    def n_devices(self) -> int:
        return self.n_pipe * self.n_data


@dataclasses.dataclass(frozen=True)
class ScheduleConfig:
    """Pipeline schedule selection.

    ``name`` in {"GPipe", "1F1B", "Interleaved1F1B"} — the same strings the
    reference dispatches on (``LLMsDistributedTrainingHelper.py:215-220``) —
    or the beyond-parity schedules "ZBH1" (zero-bubble with split
    dgrad/wgrad backward, arXiv:2401.10241) and "BFS" (breadth-first
    virtual-stage GPipe, arXiv:2211.05953).
    ``n_microbatches`` defaults to the reference's fixed 4 (``:214``).
    ``n_virtual`` is the number of virtual stages per device; the reference picks
    2 iff ``schedule=='Interleaved1F1B' and n_layers % (world_size*2)==0`` else
    1 (``:181-185``) — use :func:`virtual_stages_for` to reproduce that rule.
    """

    name: str = "GPipe"
    n_microbatches: int = 4
    n_virtual: int = 1

    def __post_init__(self):
        _check_schedule_name(self.name)

    @classmethod
    def from_artifact(cls, source, *, name: Optional[str] = None
                      ) -> "ScheduleConfig":
        """Register a certified schedule artifact (a path or parsed dict
        from ``analysis.schedule_search`` / ``scripts/search_schedule.py``)
        and return the :class:`ScheduleConfig` that selects it.

        The artifact is fully re-certified on load (recompile + cell diff
        + ``check_table``) and pinned, so ``fit``/``sweep`` runs
        under the returned config execute exactly the certified table —
        see ``parallel.schedules.register_schedule_artifact``."""
        from ..parallel.schedules import register_schedule_artifact
        cs = register_schedule_artifact(source, name=name)
        return cls(name=cs.name, n_microbatches=cs.n_microbatches,
                   n_virtual=cs.n_virtual)


# The single source of builtin names is the schedule module; re-exported here
# because config is the user-facing surface (CLIs use it for --schedule).
from ..parallel.schedules import BUILTIN_SCHEDULE_NAMES as SCHEDULE_NAMES  # noqa: E402


def _check_schedule_name(name: str) -> None:
    """Builtin or registered-custom, else ValueError listing every option."""
    from ..parallel.schedules import schedule_names
    if name not in schedule_names():
        raise ValueError(f"unknown schedule {name!r}; expected one of "
                         f"{schedule_names()}")


def virtual_stages_for(schedule_name: str, n_layers: int, n_pipe: int) -> int:
    """Reference rule for stages-per-worker (``LLMsDistributedTrainingHelper.py:181-185``).
    ZBV always runs its 2 V-placed chunks; custom registered schedules get 1
    (the reference rule only special-cases Interleaved)."""
    _check_schedule_name(schedule_name)
    if schedule_name == "ZBV":
        return 2
    # BFS gets the same 2-chunk rule as Interleaved: with V=1 it degenerates
    # to GPipe by construction (every breadth-first round is the whole
    # device ring), so sweep rows labeled BFS would silently benchmark GPipe.
    if (schedule_name in ("Interleaved1F1B", "BFS")
            and n_layers % (n_pipe * 2) == 0):
        return 2
    return 1


@dataclasses.dataclass(frozen=True)
class RunConfig:
    """One experiment's run parameters (reference: ``run_one_experiment`` kwargs,
    notebook cell 19)."""

    batch_size: int = 32
    seq_length: int = 128
    num_iterations: int = 5
    warmup_iterations: int = 2
    seed: int = 0
