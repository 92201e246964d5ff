"""Model and engine configuration for the native JAX TPU engine.

The reference delegates model execution to vLLM/SGLang/TRT-LLM
(`components/backends/*`); here the engine is first-party, so its
configuration lives in the framework. Shapes are chosen TPU-first: head
dims and block sizes aligned to MXU/VPU lanes (128 / 8), bfloat16 compute,
static bucketed shapes so every (bucket, batch) pair compiles exactly once.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax.numpy as jnp

# What ModelConfig.layer_types may name, as published.
LAYER_KINDS = ("conv", "full_attention", "sliding_attention", "linear_attention",
               "mamba", "moe")
# What a layer caches (ModelConfig.layer_kind), by its published kind ("none": a
# block that is a feed-forward alone caches nothing).
_CACHE_KIND = {"conv": "conv", "full_attention": "attention", "sliding_attention": "window",
               "linear_attention": "linear", "mamba": "ssm", "moe": "none"}
# The cache kinds whose entry is a slab a lane slot (ModelConfig.slab_shapes).
SLAB_KINDS = ("linear", "ssm")

_DTYPES = {
    "bfloat16": jnp.bfloat16,
    "float32": jnp.float32,
    "float16": jnp.float16,
}


class UnsupportedModelOption(NotImplementedError):
    """An engine option that this model's cache page or layers do not
    carry, refused at start-up. ``option`` names it (``kv_dtype``, ``tp``,
    ``pp``, ``ring_prefill``, ``spec_decode``, ``host_kv_blocks``,
    ``disk_kv_dir``, ``disagg``, ``peer_kv``, ``quant``,
    ``prefix_caching``; ``mm_embeds``, the one a request brings)."""

    def __init__(self, option: str, model: str, why: str):
        super().__init__(f"{option} is not carried for model {model!r}: {why}")
        self.option = option


@dataclass(frozen=True)
class ModelConfig:
    """Llama-family decoder-only transformer hyperparameters."""

    name: str = "llama"
    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    # None: NO rotary embedding (a model whose position reaches its attention
    # layers through recurrent ones: ``rope_parameters.rope_theta`` null).
    rope_theta: float | None = 500000.0
    rms_norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    # Byte-level models (test tokenizer) tie embeddings to save params.
    tie_embeddings: bool = False
    # Qwen2-family attention: biases on the fused qkv projection only
    # (o/gate/up/down stay bias-free, per the architecture).
    attn_qkv_bias: bool = False
    # Sparse MoE (Mixtral-style): 0 experts = dense MLP. Experts shard
    # over the mesh's model axis (expert parallelism, SURVEY.md §2.6).
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # Per-expert capacity headroom for sparse dispatch: capacity =
    # ceil(N * top_k / E * factor); tokens past it drop for that expert
    # (Switch/GShard semantics). The mixtral path's (router_scoring
    # "softmax") ONLY: the sigmoid-routed layer drops nothing.
    moe_capacity_factor: float = 2.0
    # EP dispatch mode under a mesh: "replicated" computes every token on
    # every expert shard and psums (the right trade at serving batch —
    # weights dominate ICI traffic); "alltoall" shards tokens over the
    # model axis and all-to-alls them to their expert shards (wide-EP:
    # the mode for many-host expert fleets, SURVEY.md §2.6 /
    # dsr1-wideep-h100.md:8).
    moe_dispatch: str = "replicated"
    # Looped stack (Ouro, "Scaling Latent Reasoning via Looped Language
    # Models"): the SAME num_layers weight layers run ut_steps times a
    # token, the final norm after every pass, and each (pass, layer)
    # keeps K/V of its own — num_cache_layers planes of cache for
    # num_layers layers of weights. 1 = the classic single pass.
    ut_steps: int = 1
    # Sandwich norm: a second RMSNorm on each sub-layer's OUTPUT before
    # the residual add (layers gain attn_post_norm / mlp_post_norm).
    sandwich_norm: bool = False
    # Looped models exit early once the gate's cumulated probability
    # passes this; 1.0 = never (every token takes every pass). Adaptive
    # exit makes compute per token vary and is not implemented.
    early_exit_threshold: float = 1.0
    # -- latent attention and a shared-expert sparse MLP (A.X-K1) -----------
    # "gqa": full K and V per kv head. "mla": multi-head latent attention —
    # a low-rank q (q_lora_rank, with its norm), ONE compressed K/V vector
    # a token (kv_lora_rank, with its norm) beside a rope part shared by
    # all heads (qk_rope_head_dim); the cached unit is [ckv | kr],
    # kv_lora_rank + qk_rope_head_dim values a token a layer. num_kv_heads
    # and head_dim have no say there (head_dim is kept at the q/k head's
    # whole width, qk_nope_head_dim + qk_rope_head_dim, for whoever asks).
    attention: str = "gqa"
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN, static: {"type": "yarn", "factor", "original_max_position_
    # embeddings", "beta_fast", "beta_slow", "mscale", "mscale_all_dim"}
    # (a dict is frozen to sorted items so that the config stays hashable);
    # None = plain rope. Only the latent attention reads it.
    rope_scaling: tuple | dict | None = None
    # Leading layers that keep the dense SwiGLU (width intermediate_size)
    # in a sparse model; the others' experts have width
    # moe_intermediate_size.
    first_dense_layers: int = 0
    moe_intermediate_size: int = 0
    # How the router scores: "softmax" over the chosen logits (the mixtral
    # path, capacity-bounded) or "sigmoid" per expert with the choice
    # limited to the topk_group best of n_group groups (a group's score:
    # the sum of its two highest), weights normalised over the chosen
    # (norm_topk_prob) and scaled by routed_scaling_factor.
    router_scoring: str = "softmax"
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # Shared experts of width moe_intermediate_size each, added to every
    # token's routed result.
    num_shared_experts: int = 0
    # (rank, of): this chip's share of every sparse layer's routed
    # experts, [rank * E/of, (rank + 1) * E/of) of num_experts. The router
    # stays num_experts wide; only the held experts' terms are added, and
    # none is dropped (moe_capacity_factor has no say). None: all held.
    experts_held: tuple[int, int] | None = None
    # A learned bias per expert ADDED TO THE SCORES THE CHOICE IS MADE BY
    # and to nothing else: the chosen experts' weights are their plain
    # sigmoid scores (``moe.expert_bias`` [sparse layers, E], float32).
    router_bias: bool = False
    # What the chosen scores' sum is raised by before the division.
    router_norm_eps: float = 1e-20
    # -- layers of two kinds (LFM2): gated short convolutions among GQA ------
    # One entry a layer, "conv" or "full_attention" (the published
    # names); None: every layer is attention. A conv layer keeps NO keys
    # or values: per sequence it needs the conv_L_cache - 1 newest rows of
    # its gated input ``u``, whatever the context (:meth:`kv_page_tail`).
    layer_types: tuple[str, ...] | None = None
    conv_L_cache: int = 0
    conv_bias: bool = False
    # RMSNorm over each head's values of q and of k (weights
    # ``q_layernorm`` / ``k_layernorm`` [head_dim]) BEFORE rope; with
    # ``qk_norm_over`` "projection" over the WHOLE projection of q and of k
    # (weights ``[q_size]`` / ``[kv_size]``), before the split into heads.
    qk_norm: bool = False
    qk_norm_over: str = "head"
    # The one norm of each sub-layer is on its OUTPUT, ``x + norm(f(x))``, and
    # its input is the stream as it is (``attn_norm`` / ``mlp_norm`` are then
    # those output norms); ``sandwich_norm`` has both.
    post_norm: bool = False
    # Cache 64-wide KV heads two to a 128-wide row where the geometry
    # allows (:attr:`kv_head_pairs`). The engine clears it for a dense
    # model served with an option the pair does not carry (a mesh, int8
    # pages), which then keeps the page and the options it had
    # (options._unpaired_where_not_carried).
    kv_pairing: bool = True
    # -- gated-delta-rule layers among attention layers ----------------------
    # A "linear_attention" layer (``layer_types``) keeps no K/V: per sequence
    # a FLOAT32 state ``[linear_num_value_heads, linear_key_head_dim,
    # linear_value_head_dim]`` and the ``linear_conv_kernel_dim - 1`` newest
    # rows of its depthwise convolution's input, whatever the context, in a
    # slab indexed by a LANE SLOT and not by a block id (:meth:`layer_kind`
    # "linear"; ops/linear_attention.py; model.linear_layer).
    # ``linear_allow_neg_eigval``: beta = 2 sigmoid(.), in (0, 2).
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 0
    linear_allow_neg_eigval: bool = False
    # -- Mamba-2 state-space layers, blocks of ONE sub-layer (nemotron_h) -----
    # A stack whose ``layer_types`` hold "mamba" or "moe" is one of blocks
    # ``x + f(norm(x))`` with ``f`` a mixer OR a feed-forward, never both
    # (:attr:`single_sublayer`), by ``layer_types``: "mamba" (a Mamba-2 mixer:
    # :meth:`layer_kind` "ssm", a slot of the slab a sequence as a linear
    # layer's; ops/ssm.py, model.ssm_layer), "full_attention" (its attention
    # half alone) or "moe" (the dropless sparse MLP alone: caches NOTHING,
    # :meth:`layer_kind` "none"). The one norm a block is ``attn_norm``.
    # A "mamba" layer: ``ssm_num_heads`` heads of ``ssm_head_dim`` (``d_in`` =
    # their product), ``ssm_n_groups`` groups that share ``B`` and ``C`` of
    # ``ssm_state_size``, a depthwise causal convolution of ``ssm_conv_kernel``
    # taps (a bias with ``ssm_conv_bias``) over ``[x | B | C]``, a FLOAT32 state
    # ``[ssm_num_heads, ssm_head_dim, ssm_state_size]`` a sequence; the ragged
    # shape runs in chunks of ``ssm_chunk_size``.
    ssm_num_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state_size: int = 0
    ssm_n_groups: int = 0
    ssm_conv_kernel: int = 0
    ssm_chunk_size: int = 0
    ssm_conv_bias: bool = False
    # What an expert of the dropless sparse MLP (and its shared expert) computes:
    # "swiglu" ``(silu(x Wg) * (x Wu)) Wd`` from a fused ``[Wg | Wu]``, or
    # "relu2" ``relu(x Wu)^2 Wd``: two matrices, no gate.
    mlp_activation: str = "swiglu"
    # The shared expert's whole width where it is published apart from the
    # routed experts' (``moe_shared_expert_intermediate_size``); 0:
    # ``num_shared_experts x moe_intermediate_size``.
    shared_expert_intermediate_size: int = 0
    # -- window and full attention layers mixed (Laguna) ----------------------
    # A "sliding_attention" layer's query at position p sees the keys at p -
    # sliding_window + 1 .. p (its own position counted) and no others, so
    # a sequence needs that layer's K/V of its newest sliding_window tokens
    # only: such layers keep their pages in a POOL of their own
    # (:meth:`layer_kind` "window"; EngineConfig.num_window_blocks), whose
    # blocks a sequence gives back as they slide out.
    sliding_window: int = 0
    # Query heads of each layer (``num_attention_heads_per_layer``); None:
    # num_heads everywhere. KV heads by kind: :meth:`kv_heads_of`; the key's
    # and the value's width: ``head_dim`` and :attr:`value_dim`.
    heads_per_layer: tuple[int, ...] | None = None
    # Rope parameters by published layer kind (``rope_parameters``):
    # {"full_attention": {...}, "sliding_attention": {...}}, each with
    # rope_theta, partial_rotary_factor (the share of a head's values that
    # is rotated, from the front) and rope_type "default" or "yarn" (then
    # factor, original_max_position_embeddings, beta_fast, beta_slow and
    # attention_factor, which multiplies cos and sin). Frozen to sorted
    # items like rope_scaling. None: rope_theta over the whole head.
    rope_by_kind: tuple | dict | None = None
    # A gate on each head's attention output, sigmoid(norm(x) Wg) with Wg
    # [h, heads] (``gating`` "per-head"), before the output projection.
    attn_gate: bool = False
    # -- keys wider than values, KV heads and a sink by layer kind (MiMo-V2) --
    # With attention "gqa", ``v_head_dim`` (above) > 0 states a VALUE head's
    # width beside ``head_dim``, the query's and the key's (192 beside 128):
    # :attr:`wide_key`. Such a model's pages hold exactly ``head_dim +
    # v_head_dim`` values a KV head a token, in whole lane rows
    # (ops/gqa_attention.py, "The page"), and its attention is that module's.
    # KV heads of a ``sliding_attention`` layer (``swa_num_key_value_heads``);
    # 0: ``num_kv_heads``, the full layers', on every layer.
    window_kv_heads: int = 0
    # Published layer kinds whose softmax has a SINK: a learned logit a query
    # head (``sink`` [heads], float32) that joins the row's maximum and
    # denominator and carries no value (``add_swa_attention_sink_bias``).
    attn_sinks: tuple[str, ...] = ()
    # What every value is multiplied by as it is projected
    # (``attention_value_scale``; equal to multiplying the heads' output).
    attn_value_scale: float = 1.0
    # -- generation by diffusion over blocks (SDAR) ---------------------------
    # ``block_length`` B > 0: the model generates B places at a time. A
    # query at position p sees key j iff ``j // B <= p // B``: every
    # earlier block and, BOTH ways, its own. A block starts as
    # ``mask_token_id`` at its hidden places; a denoising pass runs the
    # stack over the block's B rows, samples each hidden place from its OWN
    # row and reveals those whose confidence passes
    # ``confidence_threshold`` or, if fewer than the step's quota
    # (``B / denoising_steps``, the remainder to the first steps), the
    # quota's most confident (sampler.unmask_block); one more pass over the
    # clean block writes its K/V. 0: one next token a step, as ever.
    # ``denoising_steps`` is what a block is SERVED with (the one place
    # that says so): a deployment that serves fewer than the released
    # usage states its own in the model's configuration.
    block_length: int = 0
    denoising_steps: int = 0
    confidence_threshold: float = 1.0
    mask_token_id: int = 0

    def __post_init__(self):
        if isinstance(self.rope_scaling, dict):
            object.__setattr__(
                self, "rope_scaling", tuple(sorted(self.rope_scaling.items()))
            )
        if isinstance(self.rope_by_kind, dict):
            object.__setattr__(self, "rope_by_kind", tuple(sorted(
                (kind, tuple(sorted(dict(rp).items())))
                for kind, rp in self.rope_by_kind.items())))
        if self.heads_per_layer is not None:
            object.__setattr__(self, "heads_per_layer", tuple(self.heads_per_layer))
        if self.experts_held is not None:
            object.__setattr__(self, "experts_held", tuple(self.experts_held))
        if self.layer_types is not None:
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
        object.__setattr__(self, "attn_sinks", tuple(self.attn_sinks))
        self._check_latent_sparse()
        self._check_hybrid()
        self._check_windowed()
        self._check_block()
        if self.ut_steps < 1:
            raise ValueError(f"ut_steps={self.ut_steps} must be >= 1")
        if self.ut_steps > 1 and self.early_exit_threshold < 1.0:
            raise NotImplementedError(
                f"early_exit_threshold={self.early_exit_threshold} < 1 with "
                f"ut_steps={self.ut_steps}: adaptive exit (compute per token "
                "varies) is not implemented; every token takes every pass "
                "only at threshold 1"
            )

    def _check_latent_sparse(self) -> None:
        """A field of the latent / sigmoid-routed model that does not apply
        raises; none is silently ignored."""
        latent = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                  "qk_rope_head_dim", "v_head_dim")
        if self.attention not in ("gqa", "mla"):
            raise ValueError(f"unknown attention {self.attention!r} (gqa or mla)")
        if self.attention == "mla":
            missing = [f for f in latent if getattr(self, f) <= 0]
            if missing:
                raise ValueError(f"attention='mla' needs {missing} > 0")
            if self.attn_qkv_bias or self.sandwich_norm or self.ut_steps > 1:
                raise NotImplementedError(
                    "attention='mla' with attn_qkv_bias, sandwich_norm or "
                    "ut_steps > 1 is not implemented"
                )
            scaling = dict(self.rope_scaling or ())
            if scaling and scaling.get("type") != "yarn":
                raise NotImplementedError(
                    f"rope_scaling type {scaling.get('type')!r}: only 'yarn'"
                )
        else:
            # v_head_dim with "gqa" is a value narrower than its key: wide_key
            stray = [f for f in latent if f != "v_head_dim" and getattr(self, f)]
            if stray or self.rope_scaling is not None:
                raise ValueError(
                    f"{stray or ['rope_scaling']} set with attention='gqa': "
                    "only the latent attention reads them"
                )
        if self.router_scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown router_scoring {self.router_scoring!r}")
        sparse_only = {
            "first_dense_layers": 0, "moe_intermediate_size": 0, "n_group": 1,
            "topk_group": 1, "routed_scaling_factor": 1.0,
            "num_shared_experts": 0, "experts_held": None,
            "router_bias": False, "router_norm_eps": 1e-20,
            "mlp_activation": "swiglu", "shared_expert_intermediate_size": 0,
        }
        if not self.shared_sparse:
            stray = [f for f, d in sparse_only.items() if getattr(self, f) != d]
            if stray:
                raise ValueError(
                    f"{stray} set without moe_intermediate_size > 0 or with no "
                    "experts: only the dropless sparse MLP reads them"
                )
            if self.is_moe and self.router_scoring != "softmax":
                raise ValueError("router_scoring='sigmoid' needs moe_intermediate_size > 0")
            return
        E, k = self.num_experts, self.num_experts_per_tok
        if self.mlp_activation not in ("swiglu", "relu2"):
            raise ValueError(f"unknown mlp_activation {self.mlp_activation!r} (swiglu or relu2)")
        if self.shared_expert_intermediate_size and not self.num_shared_experts:
            raise ValueError("shared_expert_intermediate_size set with no shared expert")
        if self.router_scoring == "softmax" and (
                self.n_group > 1 or self.router_bias or self.routed_scaling_factor != 1.0):
            raise NotImplementedError(
                "router_scoring='softmax' on the dropless layer with groups, a "
                "choice bias or a scaling factor is not implemented")
        if self.single_sublayer and self.first_dense_layers:
            raise NotImplementedError(
                "first_dense_layers with 'mamba' or 'moe' layers: a block that is a dense MLP "
                "alone is not implemented")
        if not 0 <= self.first_dense_layers < self.num_layers:
            raise ValueError(
                f"first_dense_layers={self.first_dense_layers} of "
                f"{self.num_layers} layers leaves no sparse layer"
            )
        if E % self.n_group or not 1 <= self.topk_group <= self.n_group:
            raise ValueError(
                f"n_group={self.n_group} must divide num_experts={E}, and "
                f"topk_group={self.topk_group} lie in 1..n_group"
            )
        if self.n_group > 1 and E // self.n_group < 2:
            raise ValueError("a group's score is the sum of its two best experts")
        if k > self.topk_group * (E // self.n_group):
            raise ValueError(
                f"num_experts_per_tok={k} exceeds the "
                f"{self.topk_group * (E // self.n_group)} experts of the kept groups"
            )
        if not self.norm_topk_prob:
            raise NotImplementedError("norm_topk_prob=False is not implemented")
        if self.moe_dispatch != "replicated":
            raise NotImplementedError(
                "moe_dispatch is the mixtral path's; the sigmoid-routed layer "
                "runs one chip's share without an exchange"
            )
        if self.experts_held is not None:
            rank, of = self.experts_held
            if of < 1 or E % of or not 0 <= rank < of:
                raise ValueError(
                    f"experts_held={self.experts_held}: 'of' must divide "
                    f"num_experts={E} and rank lie in 0..of-1"
                )

    def _check_block(self) -> None:
        """What only a block-diffusion model reads, and the layers its
        in-block two-way mask was written for: plain GQA pages."""
        B = self.block_length
        if not B:
            stray = [f for f, d in (("denoising_steps", 0), ("confidence_threshold", 1.0),
                                    ("mask_token_id", 0)) if getattr(self, f) != d]
            if stray:
                raise ValueError(f"{stray} set with block_length=0: only a model that "
                                 "generates by blocks reads them")
            return
        if B < 1 or not 1 <= self.denoising_steps <= B:
            raise ValueError(f"block_length={B} needs 1 <= denoising_steps="
                             f"{self.denoising_steps} <= block_length")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(f"mask_token_id={self.mask_token_id} outside the vocabulary")
        if (self.latent or self.layer_types is not None or self.ut_steps > 1
                or self.kv_head_pairs or (self.is_moe and not self.shared_sparse)):
            raise NotImplementedError(
                "block_length > 0 with latent attention, layers of more than one "
                "kind, a looped stack, paired KV heads or the capacity-bounded "
                "(mixtral) MLP is not implemented: the in-block mask is the GQA "
                "layer's")

    def _check_hybrid(self) -> None:
        """``layer_types`` and what only a conv layer reads: a field that
        does not apply, or a combination no program was compared for,
        raises by name."""
        if self.qk_norm and self.latent:
            raise ValueError("qk_norm set with attention='mla': the latent "
                             "attention has norms of its own")
        if self.qk_norm_over not in ("head", "projection") or (
                self.qk_norm_over != "head" and not self.qk_norm):
            raise ValueError(f"qk_norm_over={self.qk_norm_over!r}: 'head' or, with qk_norm, "
                             "'projection'")
        if self.post_norm and (self.sandwich_norm or self.latent or self.ut_steps > 1):
            raise NotImplementedError(
                "post_norm with sandwich_norm, attention='mla' or ut_steps > 1 is not implemented")
        if self.layer_types is None:
            stray = [f for f, d in (("conv_L_cache", 0), ("conv_bias", False))
                     if getattr(self, f) != d]
            if stray:
                raise ValueError(f"{stray} set without layer_types: only a conv layer reads them")
            self._check_linear()
            self._check_ssm()
            return
        kinds = set(self.layer_types)
        if len(self.layer_types) != self.num_layers or not kinds <= set(LAYER_KINDS):
            raise ValueError(
                f"layer_types must name each of the {self.num_layers} layers "
                f"one of {LAYER_KINDS}; got {self.layer_types}"
            )
        self._check_linear()
        self._check_ssm()
        if "conv" not in kinds:
            return
        if self.conv_L_cache < 2:
            raise ValueError(f"conv_L_cache={self.conv_L_cache}: a conv layer needs >= 2 taps")
        if self.conv_bias:
            raise NotImplementedError("conv_bias=True is not implemented")
        if self.latent or self.ut_steps > 1 or self.sandwich_norm or self.attn_qkv_bias:
            raise NotImplementedError(
                "conv layers with attention='mla', ut_steps > 1, sandwich_norm "
                "or attn_qkv_bias are not implemented"
            )
        if self.is_moe and not self.shared_sparse:
            raise NotImplementedError(
                "conv layers beside the softmax-routed (mixtral) MLP are not implemented"
            )

    _LINEAR_FIELDS = ("linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
                      "linear_value_head_dim", "linear_conv_kernel_dim")

    def _check_linear(self) -> None:
        """The ``linear_*`` fields and what was compared beside a
        ``linear_attention`` layer: a field that does not apply, or a
        combination no program was compared for, raises by name."""
        if not self.linear:
            stray = [f for f in (*self._LINEAR_FIELDS, "linear_allow_neg_eigval")
                     if getattr(self, f)]
            if stray:
                raise ValueError(f"{stray} set without a 'linear_attention' layer: only "
                                 "such a layer reads them")
            return
        missing = [f for f in self._LINEAR_FIELDS if getattr(self, f) <= 0]
        if missing or self.linear_conv_kernel_dim < 2:
            raise ValueError(f"a 'linear_attention' layer needs {missing or self._LINEAR_FIELDS} "
                             "> 0 and at least 2 taps")
        if self.linear_num_key_heads != self.linear_num_value_heads:
            raise NotImplementedError(
                f"linear_num_key_heads={self.linear_num_key_heads} beside "
                f"linear_num_value_heads={self.linear_num_value_heads}: a key head shared by "
                "several value heads is not implemented")
        if (self.hybrid or self.windowed or self.latent or self.ut_steps > 1 or self.is_moe
                or self.sandwich_norm or self.attn_qkv_bias or self.kv_head_pairs
                or self.block_length or self.single_sublayer):
            raise NotImplementedError(
                "linear_attention layers with conv, sliding_attention, mamba or moe layers, "
                "attention='mla', ut_steps > 1, experts, sandwich_norm, attn_qkv_bias, "
                "paired 64-wide heads or block_length > 0 are not implemented")

    _SSM_FIELDS = ("ssm_num_heads", "ssm_head_dim", "ssm_state_size", "ssm_n_groups",
                   "ssm_conv_kernel", "ssm_chunk_size")

    def _check_ssm(self) -> None:
        """The ``ssm_*`` fields and what was compared in a stack of
        one-sub-layer blocks: a field that does not apply, or a combination no
        program was compared for, raises by name."""
        kinds = set(self.layer_types or ())
        if not self.single_sublayer:
            stray = [f for f in (*self._SSM_FIELDS, "ssm_conv_bias") if getattr(self, f)]
            if stray:
                raise ValueError(
                    f"{stray} set with no 'mamba' layer: only a 'mamba' layer reads "
                    "the ssm_* fields")
            return
        if not kinds <= {"mamba", "full_attention", "moe"} or "moe" not in kinds \
                or "mamba" not in kinds:
            raise NotImplementedError(
                f"layer_types={self.layer_types}: blocks of one "
                "sub-layer are 'mamba', 'full_attention' and 'moe' (a dense MLP alone, "
                "conv, sliding_attention and linear_attention blocks are not "
                "implemented), with at least one 'mamba' and one 'moe' layer")
        missing = [f for f in self._SSM_FIELDS if getattr(self, f) <= 0]
        if missing or self.ssm_conv_kernel < 2:
            raise ValueError(f"a 'mamba' layer needs {missing or self._SSM_FIELDS} > 0 and "
                             "at least 2 taps")
        if self.ssm_num_heads % self.ssm_n_groups:
            raise ValueError(f"ssm_n_groups={self.ssm_n_groups} must divide "
                             f"ssm_num_heads={self.ssm_num_heads}")
        if not self.shared_sparse or self.router_scoring != "sigmoid":
            raise NotImplementedError(
                "a 'moe' block is the dropless sigmoid-routed sparse MLP "
                "(moe_intermediate_size > 0, router_scoring='sigmoid'); the mixtral "
                "path and a softmax router were not compared in it")
        if (self.latent or self.ut_steps > 1 or self.sandwich_norm or self.post_norm
                or self.attn_qkv_bias or self.qk_norm or self.kv_head_pairs
                or self.block_length or self.rope_theta is not None
                or self.head_dim % 128):
            raise NotImplementedError(
                "'mamba' and 'moe' layers with attention='mla', ut_steps > 1, sandwich_norm, "
                "post_norm, attn_qkv_bias, qk_norm, paired 64-wide heads, block_length "
                "> 0, a rotary embedding (rope_theta not None) or a head that is no "
                "whole 128-lane row is not implemented")

    def _check_windowed(self) -> None:
        """``sliding_window``, ``heads_per_layer``, ``rope_by_kind``,
        ``attn_gate`` and what a wide key brings (``v_head_dim`` with "gqa",
        ``window_kv_heads``, ``attn_sinks``, ``attn_value_scale``): a field
        that does not apply, or a combination no program was compared for,
        raises by name. KV heads and the value's width by layer kind are
        STATED (:meth:`kv_heads_of`, :attr:`value_dim`), never assumed equal."""
        windowed = self.windowed
        if windowed != (self.sliding_window > 0):
            raise ValueError(
                f"sliding_window={self.sliding_window} with layer_types="
                f"{self.layer_types}: a 'sliding_attention' layer needs a window, "
                "and only such a layer reads one")
        per_layer = self.heads_per_layer is not None or self.rope_by_kind is not None
        by_kind = (self.wide_key or self.window_kv_heads or self.attn_sinks
                   or self.attn_value_scale != 1.0)
        if not (windowed or per_layer or self.attn_gate or by_kind):
            return
        if not windowed:
            raise ValueError(
                "heads_per_layer, rope_by_kind, attn_gate, v_head_dim (with "
                "attention='gqa'), window_kv_heads, attn_sinks or attn_value_scale "
                "set without a 'sliding_attention' layer: only a model with such "
                "layers reads them")
        if by_kind and not self.wide_key:
            raise NotImplementedError(
                "window_kv_heads, attn_sinks or attn_value_scale without v_head_dim: "
                "KV heads by kind, a sink and a value scale are the wide-key "
                "attention's (ops/gqa_attention.py); the library kernel has none")
        if self.wide_key:
            if 2 * self.head_dim != 3 * self.v_head_dim or self.attn_gate \
                    or any(self.kv_heads_of(k) % 2 for k in ("attention", "window")):
                raise NotImplementedError(
                    f"head_dim={self.head_dim} beside v_head_dim={self.v_head_dim}: "
                    "the wide-key page holds a key of 1.5 values' width, an even "
                    "count of KV heads a layer kind, and no attn_gate")
            if not set(self.attn_sinks) <= {"full_attention", "sliding_attention"}:
                raise ValueError(f"attn_sinks={self.attn_sinks} names no layer kind that attends")
        if self.latent or self.hybrid or self.ut_steps > 1 or self.sandwich_norm \
                or self.attn_qkv_bias or self.qk_norm or self.kv_head_pairs:
            raise NotImplementedError(
                "sliding_attention layers, heads_per_layer, rope_by_kind or "
                "attn_gate with attention='mla', conv layers, ut_steps > 1, "
                "sandwich_norm, attn_qkv_bias, qk_norm or paired 64-wide heads "
                "are not implemented")
        if self.is_moe and not self.shared_sparse:
            raise NotImplementedError(
                "these layers beside the softmax-routed (mixtral) MLP are not implemented")
        if self.heads_per_layer is not None and len(self.heads_per_layer) != self.num_layers:
            raise ValueError(
                f"heads_per_layer={self.heads_per_layer} must name each of the "
                f"{self.num_layers} layers")
        if any(
                self.heads_of(l) <= 0 or self.heads_of(l) % self.kv_heads_of(self.layer_kind(l))
                for l in range(self.num_layers)):
            raise ValueError(
                f"heads_per_layer={self.heads_per_layer or self.num_heads} must give each "
                f"of the {self.num_layers} layers a multiple of its kind's KV heads "
                f"({self.num_kv_heads} full, {self.kv_heads_of('window')} window)")
        for kind, rp in self.rope_by_kind or ():
            rp = dict(rp)
            if kind not in ("full_attention", "sliding_attention") \
                    or rp.get("rope_type", "default") not in ("default", "yarn"):
                raise ValueError(f"rope_by_kind[{kind!r}]={rp}: a layer kind that "
                                 "attends, rope_type 'default' or 'yarn'")
            if int(self.head_dim * rp.get("partial_rotary_factor", 1)) % 2:
                raise ValueError(f"rope_by_kind[{kind!r}]: an odd count of rotated values")

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def windowed(self) -> bool:
        """Some layers attend a sliding window and keep their pages in the
        window pool."""
        return self.layer_types is not None and "sliding_attention" in self.layer_types

    @property
    def wave_query_chunk(self) -> int:
        """Queries a piece, where a wave of a windowed model goes to the
        attention kernel in pieces (ops/ragged_attention.py,
        ``split_query_chunks``): the kernel walks every key a piece's table
        holds for every query of it, so a piece of a quarter window walks
        at most 1.25 windows and a page where the whole chunk of a wave
        walked window + chunk."""
        return max(1, self.sliding_window // 4)

    @property
    def layer_groups(self) -> bool:
        """The layers' operators are kept apart by kind in the parameter
        tree (``attn``, ``attn_window``, ``conv``, ``linear``), one entry a layer of the
        kind: their shapes differ."""
        return self.hybrid or self.windowed or self.linear or self.ssm

    def heads_of(self, l: int) -> int:
        """Query heads of layer ``l``."""
        return self.num_heads if self.heads_per_layer is None else self.heads_per_layer[l]

    @property
    def wide_key(self) -> bool:
        """GQA whose value head (``v_head_dim``) is narrower than its key
        and query head (``head_dim``): pages, writes and attention are
        ops/gqa_attention.py's, in both layer kinds."""
        return not self.latent and self.v_head_dim > 0

    @property
    def value_dim(self) -> int:
        """A value head's width (the key's unless ``v_head_dim`` says)."""
        return self.v_head_dim or self.head_dim

    def kv_heads_of(self, kind: str) -> int:
        """KV heads of the layers that cache ``kind`` (:meth:`layer_kind`)."""
        return self.window_kv_heads if kind == "window" and self.window_kv_heads \
            else self.num_kv_heads

    def has_sink(self, kind: str) -> bool:
        """The softmax of the layers that cache ``kind`` has a sink."""
        return any(_CACHE_KIND.get(published) == kind for published in self.attn_sinks)

    def rope_of(self, kind: str) -> dict:
        """Rope parameters of the layers of published ``kind``
        (``rope_by_kind``); the model's one ``rope_theta`` over the whole
        head where none are given."""
        for k, rp in self.rope_by_kind or ():
            if k == kind:
                return dict(rp)
        return {"rope_theta": self.rope_theta, "rope_type": "default",
                "partial_rotary_factor": 1}

    @property
    def dense_mlp_layers(self) -> tuple[int, ...]:
        """The layers whose MLP is the dense SwiGLU in a sparse model
        (``mlp_only_layers``): the leading ``first_dense_layers``."""
        return tuple(range(self.first_dense_layers))

    @property
    def hybrid(self) -> bool:
        """Some layers keep a rolling convolution state and no K/V."""
        return self.layer_types is not None and "conv" in self.layer_types

    @property
    def linear(self) -> bool:
        """Some layers keep a gated-delta-rule state a SEQUENCE (a slot of
        the slab) and no K/V."""
        return self.layer_types is not None and "linear_attention" in self.layer_types

    @property
    def ssm(self) -> bool:
        """Some layers are Mamba-2 mixers: a state a SEQUENCE (a slot of the
        slab) and no K/V."""
        return self.layer_types is not None and "mamba" in self.layer_types

    @property
    def single_sublayer(self) -> bool:
        """A block is a mixer OR a feed-forward alone, never both: what a
        "mamba" or a "moe" layer in ``layer_types`` says of the whole stack."""
        return bool({"mamba", "moe"} & set(self.layer_types or ()))

    @property
    def has_slab(self) -> bool:
        """Some layers' cache entry is a slab indexed by a lane slot: a
        sequence holds a slot while it runs (EngineCore), no block holds its
        state, and the table carries the slot (model.split_slots)."""
        return self.linear or self.ssm

    @property
    def slab_kind(self) -> str | None:
        """The cache kind of the layers that keep a slab ("linear" or "ssm")."""
        return "linear" if self.linear else "ssm" if self.ssm else None

    @property
    def ssm_inner(self) -> int:
        """``d_in`` of a "mamba" layer: heads x head width."""
        return self.ssm_num_heads * self.ssm_head_dim

    @property
    def ssm_channels(self) -> int:
        """Channels of a "mamba" layer's depthwise convolution: ``[x | B | C]``."""
        return self.ssm_inner + 2 * self.ssm_n_groups * self.ssm_state_size

    @property
    def sparse_layers(self) -> tuple[int, ...]:
        """The layers that hold the dropless sparse MLP (entry ``i`` of the
        ``moe`` group is layer ``sparse_layers[i]``'s): the "moe" blocks of a
        ``single_sublayer`` stack, else every layer behind the leading
        ``first_dense_layers``."""
        if not self.shared_sparse:
            return ()
        if self.single_sublayer:
            return self.layers_of("none")
        return tuple(range(self.first_dense_layers, self.num_layers))

    @property
    def gated_mlp(self) -> bool:
        """An expert has a gate: its first matrix is a fused ``[Wg | Wu]``."""
        return self.mlp_activation == "swiglu"

    @property
    def expert_stored_width(self) -> int:
        """Columns of a routed expert's first matrix (a gated one: of each
        half) AS STORED: ``moe_intermediate_size``, but for an un-gated expert
        rounded up to whole 128-lane rows (1,856 -> 1,920) with ZERO columns
        and zero rows of ``w_down`` behind them, so that the stream and
        grouped kernels (ops/expert_stream.py) take the layer. Exact:
        ``relu(0)^2 = 0`` times a zero row. A layout, not a width: counts of
        parameters and bytes stay at the published width."""
        im = self.moe_intermediate_size
        return im if self.gated_mlp else -(-im // 128) * 128

    @property
    def shared_expert_width(self) -> int:
        """The shared expert's whole width."""
        return (self.shared_expert_intermediate_size
                or self.num_shared_experts * self.moe_intermediate_size)

    @property
    def linear_channels(self) -> int:
        """Channels of a linear layer's depthwise convolution: ``[q | k |
        v]`` before it."""
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)

    def layer_kind(self, l: int) -> str:
        """What layer ``l`` caches: "attention" (pages of K/V, or latent
        rows, for as long as the sequence lives), "conv" (state pages),
        "window" (pages of K/V in the window pool, held while a later query
        may still see them), "linear" or "ssm" (a slot of the slab a sequence:
        :meth:`slab_shapes`) or "none" (a feed-forward block: nothing)."""
        return "attention" if self.layer_types is None else _CACHE_KIND[self.layer_types[l]]

    def layers_of(self, kind: str) -> tuple[int, ...]:
        return tuple(l for l in range(self.num_layers) if self.layer_kind(l) == kind)

    @property
    def kv_head_pairs(self) -> bool:
        """KV heads cached two to a 128-wide row (``[k_2j | k_2j+1]``,
        ``[v_2j | v_2j+1]``), so that 64-wide heads keep their published
        cache bytes AND run the attention kernel, whose lanes are 128
        (ops/ragged_attention.py, :func:`paired_heads_attention`). From the
        geometry alone, unless :attr:`kv_pairing` was cleared."""
        return (self.kv_pairing and not self.latent and self.head_dim == 64
                and self.num_kv_heads % 2 == 0)

    @property
    def cache_kv_heads(self) -> int:
        """KV heads a page of the plain layout HOLDS: ``num_kv_heads``, but
        rounded up to a count whose ``2 n`` combined heads the attention
        kernel's page tiles (1, 2, 4 or a multiple of 8 sublane rows at the
        dtype's packing: 30 heads of bfloat16 are kept as 32, the two spare
        ones zero) for a model of 128-wide heads whose layers lie in groups
        (one chip's programs, every mesh refused: no shard of heads to keep
        whole). The library kernel refuses any other count at trace time.
        What a token's K/V IS stays ``kv_unit_values_of``."""
        n = self.num_kv_heads
        if (self.latent or self.wide_key or self.kv_head_pairs or not self.layer_groups
                or self.head_dim % 128):
            return n
        packing = 4 // jnp.dtype(self.jax_dtype).itemsize

        def tiled(m: int) -> bool:
            rows, odd = divmod(2 * m, packing)
            return not odd and (rows in (1, 2, 4, 8) or rows % 8 == 0)

        return next(m for m in range(n, n + 9) if tiled(m))

    @property
    def shared_sparse(self) -> bool:
        """The DROPLESS sparse MLP (a chip's share of the routed experts of
        width ``moe_intermediate_size``, shared experts beside them, scored
        by a sigmoid or a softmax over all: ``router_scoring``), not the
        mixtral path (experts of width ``intermediate_size``,
        capacity-bounded). Told apart by the layout the tree already has,
        not by the scoring."""
        return self.is_moe and self.moe_intermediate_size > 0

    @property
    def latent(self) -> bool:
        return self.attention == "mla"

    @property
    def experts_held_range(self) -> tuple[int, int]:
        """[lo, hi) of the routed experts this chip holds."""
        if self.experts_held is None:
            return 0, self.num_experts
        rank, of = self.experts_held
        n = self.num_experts // of
        return rank * n, (rank + 1) * n

    @property
    def num_experts_held(self) -> int:
        lo, hi = self.experts_held_range
        return hi - lo

    def kv_page_tail(self, block_size: int, kind: str = "attention") -> tuple[int, ...]:
        """Trailing shape of one page of ``block_size`` tokens in one
        layer's plane, by the layer's kind (:meth:`layer_kind`).
        "attention": ``(block_size, 2 * num_kv_heads, head_dim)`` (K even,
        V odd; :attr:`cache_kv_heads` where the kernel tiles no such count); with :attr:`kv_head_pairs` ``(block_size, num_kv_heads, 2
        * head_dim)``, the same bytes with two heads a row; latent, the
        ``(rows, lanes)`` that hold ``block_size x (kv_lora_rank +
        qk_rope_head_dim)`` values (ops/latent_attention.py, "The page");
        with :attr:`wide_key` the ``(rows, lanes)`` that hold ``block_size x
        kv_heads_of(kind) x (head_dim + v_head_dim)`` (ops/gqa_attention.py,
        "The page"). "window": the "attention" page at the WINDOW layers' KV
        heads (:meth:`kv_heads_of`), in a pool of its own.
        "conv": ``(conv_L_cache - 1, h / 128, 128)``, the newest rows of
        ``u`` written in the block, each in whole 128-lane rows (a ``[2,
        h]`` tail would pad its 2 sublanes to a tile's 16); whatever
        ``block_size``, which only has to be a multiple of the slots. THE
        one place a page shape comes from (model.init_cache, the engine's
        page movers, descriptors and /health)."""
        if kind == "conv":
            slots, h = self.conv_L_cache - 1, self.hidden_size
            if block_size % slots:
                raise ValueError(
                    f"block_size={block_size} must be a multiple of "
                    f"conv_L_cache - 1 = {slots}: a position's slot is "
                    "position % slots in every block"
                )
            return (slots, h // 128, 128) if h % 128 == 0 else (slots, 1, h)
        if self.latent:
            from dynamo_tpu.ops.latent_attention import latent_page_shape

            return latent_page_shape(block_size, self.kv_lora_rank, self.qk_rope_head_dim)
        if self.wide_key:
            from dynamo_tpu.ops.gqa_attention import gqa_page_shape

            return gqa_page_shape(block_size, self.kv_heads_of(kind), self.head_dim,
                                  self.value_dim)
        if self.kv_head_pairs:
            return (block_size, self.num_kv_heads, 2 * self.head_dim)
        return (block_size, 2 * self.cache_kv_heads, self.head_dim)

    def slab_shapes(self, slots: int) -> dict[str, tuple[int, ...]]:
        """One slab layer's slab of ``slots`` lane slots (the last the
        garbage slot). A "mamba" layer: ``state [slots, H, P, N]`` FLOAT32 and
        ``conv [slots, K - 1, channels / 128, 128]`` at the model's dtype. A
        linear layer: ``state [slots, H / p, dk, p dv]``, FLOAT32 whatever
        the model's dtype, ``p`` heads side by side in a tile so that its rows
        are whole 128-lane rows (ops/linear_attention.py, "The slab": 2 at the
        published ``dv`` 192), and ``conv [slots, K - 1, channels / 128,
        128]`` at the model's dtype, the convolution's newest input rows
        oldest first in whole 128-lane rows (as the "conv" page keeps its
        own)."""
        if self.ssm:   # N is the minor dimension: whole lane rows at 128, no packing
            ch, rows = self.ssm_channels, self.ssm_conv_kernel - 1
            return {
                "state": (slots, self.ssm_num_heads, self.ssm_head_dim, self.ssm_state_size),
                "conv": (slots, rows, ch // 128, 128) if ch % 128 == 0 else (slots, rows, 1, ch),
            }
        from dynamo_tpu.ops.linear_attention import heads_per_tile

        ch, rows = self.linear_channels, self.linear_conv_kernel_dim - 1
        H, dv = self.linear_num_value_heads, self.linear_value_head_dim
        p = heads_per_tile(H, dv)
        return {
            "state": (slots, H // p, self.linear_key_head_dim, p * dv),
            "conv": (slots, rows, ch // 128, 128) if ch % 128 == 0 else (slots, rows, 1, ch),
        }

    def cache_layers(self, kind: str) -> int:
        """Page arrays of one kind (``"attention"`` planes count a looped
        model's passes): THE count beside :meth:`kv_page_tail`."""
        return len(self.layers_of(kind)) * self.ut_steps

    @property
    def cache_layer_counts(self) -> dict[str, int]:
        """``{"attention": n, "conv": n}``, as /health and /metrics give
        it; with ``"window"`` / ``"linear"`` / ``"ssm"`` and ``"none"`` for a model
        that has such layers."""
        kinds = ("attention", "conv") + (("window",) if self.windowed else ()) + (
            ("linear",) if self.linear else ()) + (("ssm", "none") if self.ssm else ())
        return {kind: self.cache_layers(kind) for kind in kinds}

    def window_bytes_per_sequence(self, block_size: int) -> int:
        """Bytes of K/V the window layers hold for one decoding sequence,
        whatever its context: ``sliding_window / block_size + 1`` blocks
        (the window's tokens, cut at block edges) in each."""
        if not self.windowed:
            return 0
        blocks = self.sliding_window // block_size + 1
        return (self.cache_layers("window") * blocks * block_size
                * self.kv_unit_values_of("window") * jnp.dtype(self.jax_dtype).itemsize)

    def state_bytes_per_block(self) -> int:
        """Bytes of convolution state one block holds over all conv
        layers (0 for a model without them)."""
        if not self.hybrid:
            return 0
        return (self.cache_layers("conv") * (self.conv_L_cache - 1)
                * self.hidden_size * jnp.dtype(self.jax_dtype).itemsize)

    def state_bytes_per_sequence(self) -> int:
        """Bytes of recurrent state one sequence holds over all slab
        layers, whatever its context: the float32 state and the
        convolution's rows (0 for a model without such layers)."""
        if self.ssm:
            return self.cache_layers("ssm") * (
                self.ssm_inner * self.ssm_state_size * 4
                + (self.ssm_conv_kernel - 1) * self.ssm_channels
                * jnp.dtype(self.jax_dtype).itemsize)
        if not self.linear:
            return 0
        H, dk, dv = (self.linear_num_value_heads, self.linear_key_head_dim,
                     self.linear_value_head_dim)
        return self.cache_layers("linear") * (
            H * dk * dv * 4 + (self.linear_conv_kernel_dim - 1) * self.linear_channels
            * jnp.dtype(self.jax_dtype).itemsize)

    def kv_unit_values_of(self, kind: str) -> int:
        """Values one token caches in one plane of the layers that cache
        ``kind`` ("attention" or "window")."""
        if self.latent:
            return self.kv_lora_rank + self.qk_rope_head_dim
        return self.kv_heads_of(kind) * (self.head_dim + self.value_dim)

    @property
    def kv_unit_values(self) -> int:
        """Values one token caches in one FULL layer's plane."""
        return self.kv_unit_values_of("attention")

    def bytes_per_block(self, block_size: int, kind: str) -> int:
        """Bytes one block of the pool of ``kind`` holds over all the layers
        that cache it, at the model's dtype: what /health and /metrics give
        by kind (MiMo at block 32: 2 x 80 KB "attention", 5 x 160 KB
        "window")."""
        n = self.cache_layers(kind)
        for dim in self.kv_page_tail(block_size, kind):
            n *= dim
        return n * jnp.dtype(self.jax_dtype).itemsize

    @property
    def num_cache_layers(self) -> int:
        """Planes of K/V a token holds: one per (pass, attention layer), in
        slot order ``u * num_layers + l`` wherever a block leaves the
        device. Equals num_layers for every single-pass model whose
        layers are all attention; a conv layer holds none
        (:meth:`cache_layers`)."""
        return self.cache_layers("attention")

    @property
    def jax_dtype(self):
        return _DTYPES[self.dtype]

    @property
    def q_size(self) -> int:
        return self.num_heads * self.head_dim

    def q_size_of(self, l: int) -> int:
        return self.heads_of(l) * self.head_dim

    @property
    def kv_size(self) -> int:
        return self.num_kv_heads * self.head_dim

    def _attn_params(self, l: int = 0) -> int:
        """Layer ``l``'s attention projections (and, latent, the two norms
        inside them; with ``attn_gate`` the gate's); the qkv bias is left
        out as before."""
        h = self.hidden_size
        if self.latent:
            H, dn, dr, dv = (self.num_heads, self.qk_nope_head_dim,
                             self.qk_rope_head_dim, self.v_head_dim)
            rq, rkv = self.q_lora_rank, self.kv_lora_rank
            return (h * rq + rq + rq * H * (dn + dr)       # wq_a, q_norm, wq_b
                    + h * (rkv + dr) + rkv                 # wkv_a, kv_norm
                    + rkv * H * (dn + dv) + H * dv * h)    # wkv_b, wo
        q_size = self.q_size_of(l)
        qk_norms = 0
        if self.qk_norm:
            qk_norms = (q_size + self.kv_size if self.qk_norm_over == "projection"
                        else 2 * self.head_dim)
        gate = h * self.heads_of(l) if self.attn_gate else 0
        if self.wide_key:   # [q | k | v] of unequal widths a kind, wo from the values' width
            kind, n = self.layer_kind(l), self.heads_of(l)
            return (h * (q_size + self.kv_unit_values_of(kind)) + n * self.value_dim * h
                    + (n if self.has_sink(kind) else 0))
        return h * (q_size + 2 * self.kv_size) + q_size * h + qk_norms + gate

    def _conv_params(self) -> int:
        """One conv layer's operator: ``in_proj [h, 3h]``, the depthwise
        taps ``[conv_L_cache, h]`` and ``out_proj [h, h]``."""
        h = self.hidden_size
        return 3 * h * h + self.conv_L_cache * h + h * h

    def _linear_params(self) -> int:
        """One linear layer's mixer: q and k ``[h, H dk]``, v, the output
        gate and the output projection ``[h, H dv]``, the two ``[h, H]`` maps
        of beta and the decay, the taps, ``A_log``, ``dt_bias`` and the
        output norm ``[dv]``."""
        h, H = self.hidden_size, self.linear_num_value_heads
        dk, dv = self.linear_key_head_dim, self.linear_value_head_dim
        return (2 * h * H * dk + 3 * h * H * dv + 2 * h * H
                + self.linear_conv_kernel_dim * self.linear_channels + 2 * H + dv)

    def _ssm_params(self) -> int:
        """One "mamba" layer's mixer at its published widths: ``in_proj [h, 2
        d_in + 2 G N + H]``, the taps and their bias, ``A_log``, ``D`` and
        ``dt_bias`` ``[H]``, the gated norm ``[d_in]`` and ``out_proj [d_in,
        h]``."""
        h, H, d_in, ch = self.hidden_size, self.ssm_num_heads, self.ssm_inner, self.ssm_channels
        return (h * (d_in + ch + H) + ch * (self.ssm_conv_kernel + self.ssm_conv_bias)
                + 3 * H + d_in + d_in * h)

    def _mlp_params(self) -> int:
        """All layers' MLP weights as HELD here: a dense SwiGLU, the
        mixtral path's router and experts, or the sigmoid-routed layer's
        router (full width), held experts and shared experts behind
        ``first_dense_layers`` dense ones (at the PUBLISHED expert width:
        :attr:`expert_stored_width` is a layout)."""
        h, i, L = self.hidden_size, self.intermediate_size, self.num_layers
        if self.shared_sparse:
            im = self.moe_intermediate_size
            mats = 3 if self.gated_mlp else 2
            sparse = (h * self.num_experts
                      + (self.num_experts if self.router_bias else 0)
                      + mats * h * (self.num_experts_held * im + self.shared_expert_width))
            return (self.first_dense_layers * 3 * h * i
                    + len(self.sparse_layers) * sparse)
        if self.is_moe:
            return L * (h * self.num_experts + self.num_experts * 3 * h * i)
        return L * 3 * h * i

    def param_bytes(self) -> int:
        """Parameter footprint at the configured dtype, of what this
        chip holds (``experts_held``)."""
        h, v = self.hidden_size, self.vocab_size
        norms = (4 if self.sandwich_norm else 1 if self.single_sublayer else 2) * h
        n_conv = len(self.layers_of("conv"))
        attn = sum(self._attn_params(l) for l in range(self.num_layers)
                   if self.layer_kind(l) in ("attention", "window"))
        total = (
            v * h + attn + len(self.layers_of("linear")) * self._linear_params()
            + (len(self.layers_of("ssm")) * self._ssm_params() if self.ssm else 0)
            + n_conv * self._conv_params() + self.num_layers * norms
            + self._mlp_params() + h + (0 if self.tie_embeddings else h * v)
        )
        if self.ut_steps > 1:
            total += h + 1                        # exit gate: w [h], b []
        bytes_per = jnp.dtype(self.jax_dtype).itemsize
        return total * bytes_per

    def quantized_param_bytes(self) -> int:
        """Footprint with int8 weight-only quantization
        (model.quantize_params: projections + lm_head at 1 byte,
        embeddings/norms at the model dtype). Sparse and latent models are
        served unquantised (model.init_params_quantized raises for them),
        so there is nothing to count."""
        if self.is_moe or self.latent or self.layer_groups:
            raise NotImplementedError(
                f"int8 weights for {self.name!r}: experts, latent projections, "
                "conv operators and layers of more than one kind are served "
                "unquantised"
            )
        h, i, v = self.hidden_size, self.intermediate_size, self.vocab_size
        proj_per_layer = (
            h * (self.q_size + 2 * self.kv_size) + self.q_size * h + 3 * h * i
        )
        bytes_per = jnp.dtype(self.jax_dtype).itemsize
        int8_bytes = self.num_layers * proj_per_layer
        norms = 4 if self.sandwich_norm else 2
        bf16_bytes = (v * h + norms * h * self.num_layers + h) * bytes_per
        if self.ut_steps > 1:
            bf16_bytes += (h + 1) * bytes_per     # exit gate
        if not self.tie_embeddings:
            int8_bytes += h * v  # lm_head quantized too
        return int8_bytes + bf16_bytes


@dataclass(frozen=True)
class EngineConfig:
    """Serving-engine shape/capacity knobs (static under jit).

    Capability parity: the knobs vLLM exposes through the reference's
    backend shims (`components/backends/vllm/src/dynamo/vllm/args.py`):
    block size, KV blocks, max seqs, max batched tokens — plus TPU-specific
    prefill length buckets (XLA compiles one program per bucket).
    """

    num_kv_blocks: int = 2048
    # Blocks of the WINDOW pool (a model with sliding_attention layers
    # only: ModelConfig.windowed), which holds those layers' pages. 0 = as
    # many as every lane decoding plus one widest prefill wave can hold
    # (:meth:`window_blocks_auto`).
    num_window_blocks: int = 0
    block_size: int = 32
    # Paged KV cache storage dtype (ISSUE 8): "bf16" keeps the classic
    # model-dtype pages (byte-for-byte the pre-quantization layout);
    # "int8" stores symmetric per-slot-per-head quantized pages with f32
    # scale metadata carried alongside (engine/kv_quant.py) — ~1.94x
    # more resident blocks at a fixed HBM budget and ~0.52x the bytes on
    # the DMA-bound decode-attention path. Quantization happens ONCE, at
    # block-write time; every tier and transfer moves the bytes verbatim.
    kv_dtype: str = "bf16"
    max_num_seqs: int = 64           # decode batch width (static)
    max_model_len: int = 8192
    prefill_buckets: tuple[int, ...] = (128, 512, 2048, 8192)
    # Sequences prefilled per dispatch (one program prefills a whole
    # admission wave; short prompts batch onto the MXU).
    prefill_batch: int = 8
    # Host KV tier (G2): blocks evicted from HBM stay cached in host RAM
    # up to this many blocks and onboard back on prefix hits. 0 = off.
    host_kv_blocks: int = 0
    # Disk KV tier (G3): host-pool evictions demote to hash-addressed
    # files under this directory (requires host_kv_blocks > 0); only
    # disk-tier eviction truly forgets a block. None = off.
    disk_kv_dir: str | None = None
    disk_kv_blocks: int = 4096
    # None: on, unless the model's cache cannot find a block again by its
    # hash (a model with window layers: off, and True is refused by name;
    # options._resolve_window_pool). The engine holds the resolved bool.
    enable_prefix_caching: bool | None = None
    # Decode batch buckets: compile decode at these widths only.
    decode_buckets: tuple[int, ...] = (8, 16, 32, 64)
    # Decode MEGASTEP (PERF.md r9): fuse this many decode iterations into
    # ONE device dispatch — an on-device scan over the ragged program
    # with device-resident sampling ((seed, counter)-keyed per inner
    # position), per-lane on-device stop flags (EOS / stop ids /
    # max-tokens; lanes that stop early run masked no-op iterations),
    # and the host draining outputs every k steps through the
    # double-buffered fetch. Amortizes the fixed per-dispatch overhead
    # by k× (PERF.md section 5 has the host's measured cost a
    # dispatch). The token stream is BIT-IDENTICAL
    # for any k (greedy and seeded sampling; host stop-scan stays the
    # authority — host-only stops roll back via num_computed_tokens).
    # 1 = off (one dispatch per decode token). UNIVERSAL (ISSUE 12):
    # every step shape rides the scanned body — chunked mixed steps
    # fuse their ragged first iteration (prefill chunks + decode rows +
    # verify rows) with k-1
    # scanned decode iterations, spec verify rows resolve accept/reject
    # ON DEVICE (rejected drafts roll back inside the dispatch via the
    # lane's position cursor), and a prefill chunk that completes its
    # prompt continues as a decode row in the same dispatch. The one
    # forced-k=1 path left is a stop watch wider than the device's
    # MEGASTEP_WATCH_W slots (surfaced as megastep_forced_single).
    megastep_k: int = 8
    # A block-diffusion model (ModelConfig.block_length > 0) runs WHOLE
    # blocks a megastep, ``ModelConfig.denoising_steps + 1`` forwards each
    # (the last writes the clean block's K/V): the engine holds
    # ``megastep_k`` resolved to the largest multiple of that it holds, at
    # least one block (options._resolve_block_megastep), so ``megastep`` is
    # the forwards a dispatch fuses for every model.

    # Sequence-parallel long-context prefill: prompts at least this long
    # (with no cached prefix) run as ONE dense ring-attention pass over
    # the engine's sp mesh instead of chunked paged waves. 0 = off.
    ring_prefill_threshold: int = 0

    # -- scheduling policy (admission shaping, PERF.md r5) ------------------
    # "waves": monolithic prefill waves run strictly before decode (the
    #   classic prefill-priority scheduler — every in-flight decode stalls
    #   for a whole wave when a prompt arrives).
    # "chunked": each step is assembled from all runnable decode sequences
    #   (q_len=1 rows) plus prefill CHUNKS of waiting prompts, under a
    #   shared max_num_batched_tokens budget — long prompts stream through
    #   several steps instead of monopolizing one, so decodes keep
    #   emitting and new arrivals stop queueing behind whole waves.
    scheduling: str = "waves"
    # Chunk size for streaming a long prompt under chunked scheduling
    # (block-aligned; non-final chunks split at block boundaries so both
    # schedulers commit identical block layouts). 0 = auto: the largest
    # prefill bucket <= max_num_batched_tokens // 4, floored at the
    # smallest bucket.
    prefill_chunk: int = 0
    # Per-step batched-token budget for mixed prefill+decode steps (each
    # decode row costs 1 token). 0 = the largest prefill bucket.
    max_num_batched_tokens: int = 0

    # -- async pipelined execution (PERF.md r8) -----------------------------
    # One-step-ahead engine loop: while step N executes on device, the
    # host plans and enqueues step N+1 (decode lanes advance exactly one
    # token, deterministically — EOS/max-tokens land one step late and
    # roll back via the num_computed_tokens cursor), sampled token ids
    # feed the next step's token buffer via an on-device gather (no
    # D2H→H2D round trip), and step N's tokens/logprobs land through a
    # double-buffered async copy consumed while N+1 runs. The token
    # stream is bit-identical on vs off (greedy AND seeded sampling).
    # None (the default): the engine chooses from what it was built with
    # (EngineCore.pipelined) — pipelined, except on an sp mesh (ring
    # prefill commits in place) and with host-drafted speculation (the
    # drafter would read history one step stale and draft nothing).
    # True / False pin a loop: the parity suites' synchronous reference,
    # tools/async_smoke.py, `--async-exec on|off`.
    async_exec: bool | None = None

    # Disaggregation: a remote-decode prefill's held blocks are released
    # if no decode worker pulls them within this window (a decode-side
    # timeout would otherwise pin them forever). 0 = never expire.
    held_block_ttl_s: float = 180.0

    # -- overload robustness (ISSUE 10) ------------------------------------
    # Per-tenant weighted fair queueing in the admission queue: requests
    # are admitted by deficit-round-robin over prompt-token cost across
    # tenants (engine/fair_queue.py) instead of strict FIFO, so one
    # flooding tenant cannot starve the rest. Off keeps exact FIFO; for
    # a single tenant DRR degenerates to FIFO, so the token stream is
    # bit-identical on vs off (pinned by tests/test_overload.py).
    fair_scheduling: bool = False
    # Tokens a tenant earns per DRR rotation visit. 0 = auto (the
    # resolved per-step token budget — one quantum admits roughly one
    # step's worth of prefill per tenant per round).
    fair_quantum: int = 0
    # Bounded admission queue (backpressure): add_request refuses new
    # work with a typed, RETRYABLE EngineOverloadedError once this many
    # requests are queued (inbox + waiting) — peers route the request to
    # another instance via the migration machinery instead of piling
    # unboundedly here. 0 = unbounded (legacy).
    max_waiting: int = 0

    # -- speculative decoding (dynamo_tpu/spec) -----------------------------
    # "off": every decode row is q_len=1. "ngram": decode rows draft up to
    #   spec_k tokens via prompt-lookup and verify pending+draft as ONE
    #   q_len<=spec_k+1 ragged row; accepted tokens emit in one step.
    #   Output is bit-identical to spec off (greedy AND seeded sampling) —
    #   verification replays the target's own per-lane counter-keyed
    #   choices. Requests may override per-call via dyn.spec_decode.
    spec_decode: str = "off"
    # Max draft tokens per verify step; also the clamp for per-request k
    # (the verify program's sample-gather width is static: spec_k + 1).
    spec_k: int = 4
    # Prompt-lookup suffix lengths tried (longest first) and the history
    # window searched.
    spec_ngram_min: int = 1
    spec_ngram_max: int = 3
    spec_window: int = 1024
    # Draft ON DEVICE between megastep inner iterations: each speculating
    # lane carries a packed prompt+output history ring through the scanned
    # body, suffix-matches it after every accept/reject, and verifies the
    # fresh draft in the next inner iteration — draft→verify→accept loops
    # inside ONE dispatch, so accepted depth compounds to
    # 1 + (megastep-1)·(spec_k+1) tokens per dispatch. The device matcher
    # replays spec/ngram.py's proposal exactly (longest suffix first, most
    # recent occurrence, window bound) or proposes nothing, so the stream
    # stays bit-identical to host drafting and to spec off. Requires
    # megastep >= 2 to change anything (the loop lives between inner
    # iterations); lanes degrade to host drafting per dispatch when block
    # pressure cannot reserve the worst-case accepted depth.
    spec_device_draft: bool = False

    @property
    def kv_quantized(self) -> bool:
        """True when the paged KV cache stores int8 pages + scales."""
        return self.kv_dtype == "int8"

    @property
    def megastep(self) -> int:
        """Decode-megastep length (inner iterations per device
        dispatch)."""
        return self.megastep_k

    @property
    def max_blocks_per_seq(self) -> int:
        return (self.max_model_len + self.block_size - 1) // self.block_size

    def window_span_blocks(self, window: int, tokens: int) -> int:
        """Blocks that hold what ``tokens`` consecutive queries of one
        sequence see through a window of ``window``, and write: positions
        ``p - window + 1 .. p + tokens - 1`` for a first query at ``p``,
        wherever ``p`` lies in its block."""
        return (window + tokens - 2) // self.block_size + 2

    def window_table_blocks(self, window: int) -> int:
        """Columns of a sequence's window table, one width for every
        program: the span of the most queries a sequence has in ONE
        dispatch, a prefill chunk of the largest bucket that goes on as a
        decode row for the rest of a megastep."""
        return self.window_span_blocks(
            window, self.prefill_buckets[-1] + self.megastep_k - 1)

    def window_blocks_auto(self, window: int) -> int:
        """``num_window_blocks`` where it is left 0: every lane's decode
        span, and one widest wave's tokens behind ``prefill_batch``
        cursors."""
        return (self.max_num_seqs * self.window_span_blocks(window, self.megastep_k)
                + self.prefill_buckets[-1] // self.block_size + 2 * self.prefill_batch)

    @property
    def token_budget(self) -> int:
        """Resolved per-step batched-token budget (chunked scheduling)."""
        return self.max_num_batched_tokens or self.prefill_buckets[-1]

    @property
    def fair_quantum_resolved(self) -> int:
        """Resolved DRR quantum (tokens per tenant per rotation visit)."""
        return self.fair_quantum or self.token_budget

    @property
    def chunk_size(self) -> int:
        """Resolved prefill chunk size (block-aligned by validation)."""
        if self.prefill_chunk:
            return self.prefill_chunk
        target = max(self.token_budget // 4, self.prefill_buckets[0])
        fitting = [b for b in self.prefill_buckets if b <= target]
        return fitting[-1] if fitting else self.prefill_buckets[0]

    @property
    def total_slots(self) -> int:
        # One extra garbage block at index `num_kv_blocks` absorbs writes
        # from padded positions, keeping every jitted shape static.
        return (self.num_kv_blocks + 1) * self.block_size

    @property
    def garbage_block(self) -> int:
        return self.num_kv_blocks

    @property
    def state_slots(self) -> int:
        """Lane slots of a linear layer's slab (ModelConfig.slab_shapes): one
        a sequence that can run at once, and the garbage slot."""
        return self.max_num_seqs + 1

    @property
    def garbage_slot(self) -> int:
        """The slab's last slot: what padding rows, dead megastep iterations
        and dead lanes read (as zeros) and write."""
        return self.max_num_seqs


# -- presets ---------------------------------------------------------------

def llama3_8b() -> ModelConfig:
    return ModelConfig(name="llama3-8b")


def llama3_70b() -> ModelConfig:
    return ModelConfig(
        name="llama3-70b",
        hidden_size=8192,
        intermediate_size=28672,
        num_layers=80,
        num_heads=64,
        num_kv_heads=8,
    )


def llama3_1b() -> ModelConfig:
    """Llama-3.2-1B-proportioned single-chip flagship.

    TPU-native deviation: 16 heads x 128 head_dim instead of upstream's
    32 x 64 — the Pallas paged-attention kernel DMAs KV pages whose lane
    dimension is head_dim, and TPU tiling wants 128 there. Same hidden
    size, same FLOPs; models with head_dim < 128 still run via the XLA
    reference attention path.
    """
    return ModelConfig(
        name="llama3-1b",
        hidden_size=2048,
        intermediate_size=8192,
        num_layers=16,
        num_heads=16,
        num_kv_heads=8,
        head_dim=128,
        tie_embeddings=True,
    )


def qwen2_7b() -> ModelConfig:
    """Qwen2.5-7B: GQA llama-family body + qkv biases (the family's one
    architectural delta; reference serves Qwen through its engines, e.g.
    the DSR1-distill recipes)."""
    return ModelConfig(
        name="qwen2-7b",
        vocab_size=152064,
        hidden_size=3584,
        intermediate_size=18944,
        num_layers=28,
        num_heads=28,
        num_kv_heads=4,
        head_dim=128,
        rope_theta=1000000.0,
        rms_norm_eps=1e-6,
        attn_qkv_bias=True,
    )


def ouro_2_6b() -> ModelConfig:
    """Ouro-2.6B (ByteDance, model_type "ouro"): 48 plain multi-head
    layers with sandwich norms, run 4 times a token — 192 planes of K/V
    (1.5 MB a token in bf16) for 5.3 GB of weights."""
    return ModelConfig(
        name="ouro-2.6b",
        vocab_size=49152,
        hidden_size=2048,
        intermediate_size=5632,
        num_layers=48,
        num_heads=16,
        num_kv_heads=16,
        head_dim=128,
        rope_theta=1000000.0,
        rms_norm_eps=1e-6,
        ut_steps=4,
        sandwich_norm=True,
    )


def mixtral_8x7b() -> ModelConfig:
    return ModelConfig(
        name="mixtral-8x7b",
        vocab_size=32000,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=1000000.0,
        num_experts=8,
        num_experts_per_tok=2,
    )


def tiny_moe(vocab_size: int = 384) -> ModelConfig:
    return ModelConfig(
        name="tiny-moe",
        vocab_size=vocab_size,
        hidden_size=64,
        intermediate_size=96,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        rope_theta=10000.0,
        dtype="float32",
        tie_embeddings=True,
        num_experts=4,
        num_experts_per_tok=2,
    )


def axk1_ep16() -> ModelConfig:
    """A.X-K1 (SKT, model_type "axk1") as ONE chip of sixteen holds it:
    latent attention whole, 12 of each sparse layer's 192 routed experts
    (rank 0) beside the shared one, an eighth of the vocabulary, one
    dense and six sparse layers of the 61 (the rest lie on further
    chips as pipeline stages). 9.68 GB in bf16."""
    return ModelConfig(
        name="a.x-k1-ep16",
        vocab_size=20480,
        hidden_size=7168,
        intermediate_size=18432,
        num_layers=7,
        num_heads=64,
        num_kv_heads=64,
        head_dim=192,
        rope_theta=10000.0,
        rms_norm_eps=1e-6,
        attention="mla",
        q_lora_rank=1536,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        rope_scaling={
            "type": "yarn", "factor": 32, "beta_fast": 32, "beta_slow": 1,
            "mscale": 1, "mscale_all_dim": 1,
            "original_max_position_embeddings": 4096,
        },
        first_dense_layers=1,
        moe_intermediate_size=2048,
        num_experts=192,
        num_experts_per_tok=8,
        router_scoring="sigmoid",
        n_group=8,
        topk_group=4,
        routed_scaling_factor=2.5,
        num_shared_experts=1,
        experts_held=(0, 16),
    )


def tiny_axk1(vocab_size: int = 384, experts_held=(0, 4)) -> ModelConfig:
    """A.X-K1's shape at test size: latent attention with YaRN, one dense
    layer then two sigmoid-routed ones (16 experts in 4 groups of which
    2, 4 a token, one shared), this chip holding a quarter of them."""
    return ModelConfig(
        name="tiny-axk1",
        vocab_size=vocab_size,
        hidden_size=64,
        intermediate_size=160,
        num_layers=3,
        num_heads=4,
        num_kv_heads=4,
        head_dim=24,
        rope_theta=10000.0,
        rms_norm_eps=1e-6,
        dtype="float32",
        attention="mla",
        q_lora_rank=48,
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        rope_scaling={
            "type": "yarn", "factor": 32, "beta_fast": 32, "beta_slow": 1,
            "mscale": 1, "mscale_all_dim": 1,
            "original_max_position_embeddings": 64,
        },
        first_dense_layers=1,
        moe_intermediate_size=32,
        num_experts=16,
        num_experts_per_tok=4,
        router_scoring="sigmoid",
        n_group=4,
        topk_group=2,
        routed_scaling_factor=2.5,
        num_shared_experts=1,
        experts_held=experts_held,
    )


_LFM2_PERIOD = ("full_attention", "conv", "conv", "conv")


def lfm2_24b_a2b_10l() -> ModelConfig:
    """LFM2-24B-A2B (LiquidAI, model_type "lfm2_moe") as stage 0 of a
    four-stage pipeline holds it: layers 0-9 of the 40 whole (two dense
    conv layers, then two periods of attention-conv-conv-conv with all
    64 bias-chosen experts of width 1536, 4 a token), the whole
    vocabulary, tied embeddings. 32 query heads on 8 KV heads of width
    64, QK-norm, three-tap gated convolutions. 10.53 GB in bf16."""
    return ModelConfig(
        name="lfm2-24b-a2b-10l",
        vocab_size=65536,
        hidden_size=2048,
        intermediate_size=11776,
        num_layers=10,
        num_heads=32,
        num_kv_heads=8,
        head_dim=64,
        rope_theta=1000000.0,
        rms_norm_eps=1e-5,
        tie_embeddings=True,
        layer_types=("conv", "conv") + 2 * _LFM2_PERIOD,
        conv_L_cache=3,
        qk_norm=True,
        first_dense_layers=2,
        moe_intermediate_size=1536,
        num_experts=64,
        num_experts_per_tok=4,
        router_scoring="sigmoid",
        router_bias=True,
        router_norm_eps=1e-6,
    )


def tiny_lfm2(vocab_size: int = 384) -> ModelConfig:
    """LFM2's shape at test size: two dense conv layers, then
    attention-conv-conv-conv with 8 bias-chosen experts, 2 a token; 4
    query heads on 2 KV heads of width 64 (so that the heads are cached
    in pairs, as at the published width)."""
    return ModelConfig(
        name="tiny-lfm2",
        vocab_size=vocab_size,
        hidden_size=256,
        intermediate_size=320,
        num_layers=6,
        num_heads=4,
        num_kv_heads=2,
        head_dim=64,
        rope_theta=10000.0,
        rms_norm_eps=1e-5,
        dtype="float32",
        tie_embeddings=True,
        layer_types=("conv", "conv") + _LFM2_PERIOD,
        conv_L_cache=3,
        qk_norm=True,
        first_dense_layers=2,
        moe_intermediate_size=64,
        num_experts=8,
        num_experts_per_tok=2,
        router_scoring="sigmoid",
        router_bias=True,
        router_norm_eps=1e-6,
    )


def sdar_30b_a3b_6l() -> ModelConfig:
    """SDAR-30B-A3B-Chat (JetLM, model_type "sdar_moe") as stage 0 of an
    eight-stage pipeline holds it: layers 0-5 of the 48 whole, all 128
    softmax-routed experts of width 768 (8 a token, the chosen scores
    normalised), the whole vocabulary, untied; 32 query heads on 4 KV heads
    of width 128, QK-norm. Generation by diffusion over blocks of 4 places,
    the surest first, every place over a confidence of 0.9 at once; served
    with 2 denoising steps where the released usage has 4 (the threshold
    of trained weights reveals more than a step's quota; the benchmark's
    random weights never reach it). 8.72 GB in bf16."""
    return ModelConfig(
        name="sdar-30b-a3b-6l",
        vocab_size=151936,
        hidden_size=2048,
        intermediate_size=6144,
        num_layers=6,
        num_heads=32,
        num_kv_heads=4,
        head_dim=128,
        rope_theta=1000000.0,
        rms_norm_eps=1e-6,
        qk_norm=True,
        moe_intermediate_size=768,
        num_experts=128,
        num_experts_per_tok=8,
        router_scoring="softmax",
        block_length=4,
        denoising_steps=2,
        confidence_threshold=0.9,
        mask_token_id=151669,
    )


def tiny_sdar(vocab_size: int = 384, **changes) -> ModelConfig:
    """SDAR's shape at test size: GQA with QK-norm, 8 softmax-routed
    experts, 2 a token, blocks of 4 places."""
    fields = dict(
        name="tiny-sdar",
        vocab_size=vocab_size,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        rope_theta=10000.0,
        rms_norm_eps=1e-6,
        dtype="float32",
        qk_norm=True,
        moe_intermediate_size=32,
        num_experts=8,
        num_experts_per_tok=2,
        router_scoring="softmax",
        block_length=4,
        denoising_steps=2,
        confidence_threshold=0.9,
        mask_token_id=vocab_size - 1,
    )
    fields.update(changes)
    return ModelConfig(**fields)


_LAGUNA_PERIOD = ("full_attention",) + 3 * ("sliding_attention",)
_LAGUNA_ROPE = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1, "beta_fast": 32,
        "attention_factor": 1.4852030263919618, "partial_rotary_factor": 0.5,
    },
    "sliding_attention": {
        "rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1,
    },
}


def laguna_s21_ep8_9l() -> ModelConfig:
    """Laguna-S-2.1 (poolside, model_type "laguna") as ONE chip of eight
    holds its first stage: layers 0-8 of the 48 (full attention at 0, 4, 8
    with 48 query heads and YaRN over half of each head; window-512
    attention at 1-3 and 5-7 with 72; 8 KV heads of 128 on all; a per-head
    output gate), the leading dense layer, then eight sparse layers with
    32 of each one's 256 sigmoid-routed experts (rank 0, 10 a token)
    beside the shared one, an eighth of the vocabulary. 6.40 GB in bf16."""
    return ModelConfig(
        name="laguna-s-2.1-ep8-9l",
        vocab_size=12544,
        hidden_size=3072,
        intermediate_size=12288,
        num_layers=9,
        num_heads=48,
        num_kv_heads=8,
        head_dim=128,
        rms_norm_eps=1e-6,
        layer_types=(2 * _LAGUNA_PERIOD + ("full_attention",)),
        heads_per_layer=(48, 72, 72, 72, 48, 72, 72, 72, 48),
        sliding_window=512,
        rope_by_kind=_LAGUNA_ROPE,
        attn_gate=True,
        first_dense_layers=1,
        moe_intermediate_size=1024,
        num_experts=256,
        num_experts_per_tok=10,
        router_scoring="sigmoid",
        routed_scaling_factor=2.5,
        num_shared_experts=1,
        experts_held=(0, 8),
    )


def tiny_laguna(vocab_size: int = 384, experts_held=(0, 2)) -> ModelConfig:
    """Laguna's shape at test size: one dense layer then four sparse ones,
    one whole period (full attention with 4 query heads and YaRN over half
    a head, then three window-8 layers with 6; 2 KV heads of 16; the
    per-head gate), 8 sigmoid-routed experts of which 3 a token beside a
    shared one, this chip holding half of them."""
    rope = {k: dict(v) for k, v in _LAGUNA_ROPE.items()}
    rope["full_attention"].update(factor=8, original_max_position_embeddings=32)
    return ModelConfig(
        name="tiny-laguna",
        vocab_size=vocab_size,
        hidden_size=64,
        intermediate_size=160,
        num_layers=5,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        rms_norm_eps=1e-6,
        dtype="float32",
        layer_types=_LAGUNA_PERIOD + ("full_attention",),
        heads_per_layer=(4, 6, 6, 6, 4),
        sliding_window=8,
        rope_by_kind=rope,
        attn_gate=True,
        first_dense_layers=1,
        moe_intermediate_size=32,
        num_experts=8,
        num_experts_per_tok=3,
        router_scoring="sigmoid",
        routed_scaling_factor=2.5,
        num_shared_experts=1,
        experts_held=experts_held,
    )


_MIMO_ROPE = {
    # a third of each head rotated, from the front: int(192 x 0.334) = 64
    "full_attention": {"rope_theta": 10000000, "rope_type": "default",
                       "partial_rotary_factor": 0.334},
    "sliding_attention": {"rope_theta": 10000, "rope_type": "default",
                          "partial_rotary_factor": 0.334},
}
_F, _W = "full_attention", "sliding_attention"


def mimo_v25_ep16_7l() -> ModelConfig:
    """MiMo-V2.5 (Xiaomi, model_type "mimo_v2") as ONE chip of sixteen holds
    its first stage: layers 0-6 of the 48 (full attention at 0 and 5 on 4 KV
    heads, window-128 attention with a sink logit a head at 1-4 and 6 on 8;
    64 query heads, keys 192 wide beside values 128 wide, a third of each
    head rotated, values scaled by 0.707), the leading dense layer, then six
    sparse layers with 16 of each one's 256 sigmoid-routed, bias-chosen
    experts (rank 0, 8 a token, no shared one), an eighth of the
    vocabulary. 6.86 GB in bf16."""
    return ModelConfig(
        name="mimo-v2.5-ep16-7l",
        vocab_size=19072,
        hidden_size=4096,
        intermediate_size=16384,
        num_layers=7,
        num_heads=64,
        num_kv_heads=4,
        head_dim=192,
        v_head_dim=128,
        rms_norm_eps=1e-5,
        layer_types=(_F, _W, _W, _W, _W, _F, _W),
        sliding_window=128,
        window_kv_heads=8,
        attn_sinks=(_W,),
        attn_value_scale=0.707,
        rope_by_kind=_MIMO_ROPE,
        first_dense_layers=1,
        moe_intermediate_size=2048,
        num_experts=256,
        num_experts_per_tok=8,
        router_scoring="sigmoid",
        router_bias=True,
        experts_held=(0, 16),
    )


def tiny_mimo(vocab_size: int = 384, experts_held=(0, 4)) -> ModelConfig:
    """MiMo's shape at test size, every ratio kept: keys 24 wide beside
    values 16 wide, 32 query heads on 2 KV heads (full, groups of 16) and 4
    (window 8 with a sink, groups of 8), a third of a head rotated; one
    dense layer then four sparse ones of 16 bias-chosen experts, 4 a token,
    this chip holding a quarter of them."""
    return ModelConfig(
        name="tiny-mimo",
        vocab_size=vocab_size,
        hidden_size=64,
        intermediate_size=160,
        num_layers=5,
        num_heads=32,
        num_kv_heads=2,
        head_dim=24,
        v_head_dim=16,
        rms_norm_eps=1e-5,
        dtype="float32",
        layer_types=(_F, _W, _W, _F, _W),
        sliding_window=8,
        window_kv_heads=4,
        attn_sinks=(_W,),
        attn_value_scale=0.707,
        rope_by_kind=_MIMO_ROPE,
        first_dense_layers=1,
        moe_intermediate_size=32,
        num_experts=16,
        num_experts_per_tok=4,
        router_scoring="sigmoid",
        router_bias=True,
        experts_held=experts_held,
    )


def tiny_loop(vocab_size: int = 384) -> ModelConfig:
    """The looped stack (Ouro's shape) at test size: 3 layers x 3 passes."""
    return ModelConfig(
        name="tiny-loop",
        vocab_size=vocab_size,
        hidden_size=64,
        intermediate_size=128,
        num_layers=3,
        num_heads=4,
        num_kv_heads=4,
        head_dim=16,
        rope_theta=10000.0,
        rms_norm_eps=1e-6,
        dtype="float32",
        ut_steps=3,
        sandwich_norm=True,
    )


def tiny_model(vocab_size: int = 384) -> ModelConfig:
    """Byte-tokenizer-sized model for tests and CPU smoke runs."""
    return ModelConfig(
        name="tiny",
        vocab_size=vocab_size,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        rope_theta=10000.0,
        dtype="float32",
        tie_embeddings=True,
    )


def tiny_engine(**overrides) -> EngineConfig:
    defaults = dict(
        num_kv_blocks=64,
        block_size=8,
        max_num_seqs=8,
        max_model_len=256,
        prefill_buckets=(32, 64, 128),  # < max_model_len: exercises chunking
        decode_buckets=(4, 8),
    )
    defaults.update(overrides)
    return EngineConfig(**defaults)


_OLMO_HYBRID_PERIOD = ("linear_attention",) * 3 + ("full_attention",)


def olmo_hybrid_7b_pp2_16l() -> ModelConfig:
    """Olmo-Hybrid-7B (allenai, model_type "olmo_hybrid") as stage 0 of a
    two-stage pipeline holds it: layers 0-15 of the 32 whole, four periods
    of three gated-delta-rule layers (30 heads, keys 96 and values 192 wide,
    a four-tap depthwise convolution on q, k and v, beta in (0, 2)) and one
    multi-head attention layer (30 heads of 128 on 30 KV heads, RMSNorm over
    the whole q and k projections, NO rotary embedding), a dense SwiGLU of
    11,008 in every layer, each sub-layer normed on its output, the whole
    vocabulary untied. 8.20 GB in bf16."""
    return ModelConfig(
        name="olmo-hybrid-7b-pp2-16l",
        vocab_size=100352,
        hidden_size=3840,
        intermediate_size=11008,
        num_layers=16,
        num_heads=30,
        num_kv_heads=30,
        head_dim=128,
        rope_theta=None,
        rms_norm_eps=1e-6,
        layer_types=4 * _OLMO_HYBRID_PERIOD,
        qk_norm=True,
        qk_norm_over="projection",
        post_norm=True,
        linear_num_key_heads=30,
        linear_num_value_heads=30,
        linear_key_head_dim=96,
        linear_value_head_dim=192,
        linear_conv_kernel_dim=4,
        linear_allow_neg_eigval=True,
    )


def tiny_olmo_hybrid(vocab_size: int = 384) -> ModelConfig:
    """Olmo-Hybrid's shape at test size: a period of three gated-delta-rule
    layers (2 heads, keys 32 and values 64 wide, four taps) and one
    attention layer of 3 heads of 128 (a count the kernel's page does not
    tile: kept as 4, :attr:`ModelConfig.cache_kv_heads`) with
    whole-projection QK-norm and no rope, then one more linear layer;
    float32."""
    return ModelConfig(
        name="tiny-olmo-hybrid",
        vocab_size=vocab_size,
        hidden_size=384,
        intermediate_size=320,
        num_layers=5,
        num_heads=3,
        num_kv_heads=3,
        head_dim=128,
        rope_theta=None,
        rms_norm_eps=1e-6,
        dtype="float32",
        layer_types=_OLMO_HYBRID_PERIOD + ("linear_attention",),
        qk_norm=True,
        qk_norm_over="projection",
        post_norm=True,
        linear_num_key_heads=2,
        linear_num_value_heads=2,
        linear_key_head_dim=32,
        linear_value_head_dim=64,
        linear_conv_kernel_dim=4,
        linear_allow_neg_eigval=True,
    )


# ``hybrid_override_pattern`` "MEMEM*E": the period nemotron_h's 52 layers open
# with five times over (M a Mamba-2 mixer, E experts, * attention).
_NEMOTRON_PERIOD = ("mamba", "moe", "mamba", "moe", "mamba", "full_attention", "moe")


def nemotron3_nano_ep2_14l() -> ModelConfig:
    """NVIDIA-Nemotron-3-Nano-30B-A3B (model_type "nemotron_h") as ONE chip
    of a v5e-8 holds it, two chips sharing each layer's experts and four
    pipeline stages the depth: layers 0-13 of the 52 (two periods ``MEMEM*E``:
    6 Mamba-2 mixers of 64 heads x 64 with a 128-wide state in 8 groups, 6
    expert blocks, 2 attention blocks of 32 heads over 2 KV heads of 128 with
    NO rotary embedding), each block ONE sub-layer; experts 0-63 of each
    layer's 128 (sigmoid scores, a bias on the choice, 6 a token, x 2.5,
    ``relu(x Wu)^2 Wd`` 1,856 wide) beside a shared expert 3,712 wide; the
    whole vocabulary untied. 9.87 GB in bf16 at the published widths (the
    routed experts are STORED 1,920 wide: ``expert_stored_width``)."""
    return ModelConfig(
        name="nemotron-3-nano-30b-a3b-ep2-14l",
        vocab_size=131072,
        hidden_size=2688,
        intermediate_size=1856,
        num_layers=14,
        num_heads=32,
        num_kv_heads=2,
        head_dim=128,
        rope_theta=None,
        rms_norm_eps=1e-5,
        layer_types=2 * _NEMOTRON_PERIOD,
        ssm_num_heads=64,
        ssm_head_dim=64,
        ssm_state_size=128,
        ssm_n_groups=8,
        ssm_conv_kernel=4,
        ssm_chunk_size=128,
        ssm_conv_bias=True,
        num_experts=128,
        num_experts_per_tok=6,
        moe_intermediate_size=1856,
        router_scoring="sigmoid",
        routed_scaling_factor=2.5,
        num_shared_experts=1,
        shared_expert_intermediate_size=3712,
        experts_held=(0, 2),
        router_bias=True,
        mlp_activation="relu2",
    )


def tiny_nemotron_h(vocab_size: int = 384, experts_held=(0, 2)) -> ModelConfig:
    """nemotron_h's shape at test size: the period ``MEMEM*E`` of one-sub-layer
    blocks (Mamba-2 of 4 heads x 16 with a 32-wide state in 2 groups, chunks
    of 16 rows; attention of 2 heads over 1 KV head of 128, no rope; 8
    experts, 2 a token, half of them held, ``relu^2`` 96 wide (stored 128)
    beside a shared expert 192 wide); float32."""
    return ModelConfig(
        name="tiny-nemotron-h",
        vocab_size=vocab_size,
        hidden_size=256,
        intermediate_size=96,
        num_layers=7,
        num_heads=2,
        num_kv_heads=1,
        head_dim=128,
        rope_theta=None,
        rms_norm_eps=1e-5,
        dtype="float32",
        layer_types=_NEMOTRON_PERIOD,
        ssm_num_heads=4,
        ssm_head_dim=16,
        ssm_state_size=32,
        ssm_n_groups=2,
        ssm_conv_kernel=4,
        ssm_chunk_size=16,
        ssm_conv_bias=True,
        num_experts=8,
        num_experts_per_tok=2,
        moe_intermediate_size=96,
        router_scoring="sigmoid",
        routed_scaling_factor=2.5,
        num_shared_experts=1,
        shared_expert_intermediate_size=192,
        experts_held=experts_held,
        router_bias=True,
        mlp_activation="relu2",
    )


PRESETS = {
    "llama3-8b": llama3_8b,
    "llama3-70b": llama3_70b,
    "llama3-1b": llama3_1b,
    "qwen2-7b": qwen2_7b,
    "mixtral-8x7b": mixtral_8x7b,
    "ouro-2.6b": ouro_2_6b,
    "a.x-k1-ep16": axk1_ep16,
    "lfm2-24b-a2b-10l": lfm2_24b_a2b_10l,
    "laguna-s-2.1-ep8-9l": laguna_s21_ep8_9l,
    "sdar-30b-a3b-6l": sdar_30b_a3b_6l,
    "mimo-v2.5-ep16-7l": mimo_v25_ep16_7l,
    "olmo-hybrid-7b-pp2-16l": olmo_hybrid_7b_pp2_16l,
    "nemotron-3-nano-30b-a3b-ep2-14l": nemotron3_nano_ep2_14l,
    "tiny": tiny_model,
    "tiny-moe": tiny_moe,
    "tiny-loop": tiny_loop,
    "tiny-axk1": tiny_axk1,
    "tiny-lfm2": tiny_lfm2,
    "tiny-laguna": tiny_laguna,
    "tiny-sdar": tiny_sdar,
    "tiny-mimo": tiny_mimo,
    "tiny-olmo-hybrid": tiny_olmo_hybrid,
    "tiny-nemotron-h": tiny_nemotron_h,
}
