"""Decoder-only language models from a catalog-style config dict, so that the
next one is a dict and not a class.

``decoder_lm.from_config(config)`` reads the keys a model's public ``config.json`` uses.
Five families so far, told apart by their own keys, the first in two key sets
(the cuts to one chip are ``benchmark/configs/mellum2_12b.json``,
``laguna_s_2_1.json``, ``granite_4_0_h_micro.json``, ``joyai_llm_flash.json``,
``nemotron_3_nano_30b_a3b.json`` and ``olmo_hybrid_7b.json``)::

    vocab_size, hidden_size, num_hidden_layers, layer_types,
    num_attention_heads, num_key_value_heads, head_dim, rms_norm_eps

    sparse, windowed (Mellum2-12B-A2.5B-Instruct):
    sliding_window, rope_parameters, num_experts, num_experts_per_tok,
    moe_intermediate_size, norm_topk_prob, mlp_layer_types

    the same with gated attention, a dense leading layer and a shared expert
    (Laguna-S-2.1; Qwen3-MoE's and Qwen3-Next's keys beside its own):
    num_attention_heads_per_layer (query heads layer by layer), gating
    ("per-head") and gating_types ("per_head" each: one sigmoid a head and
    token on the attention's output), rope_parameters' partial_rotary_factor
    (the rotated share of each head), intermediate_size, mlp_only_layers and
    decoder_sparse_step (the dense layers, which mlp_layer_types states
    too), shared_expert_intermediate_size (a shared expert behind a sigmoid
    gate), moe_routed_scaling_factor (the softmax router's chosen weights
    times it); moe_router_logit_softcapping 0 and
    moe_apply_router_weight_on_input false, the only values built

    state-space hybrid (granite-4.0-h-micro; ``layer_types`` of ``mamba`` and
    ``attention``, ``num_local_experts`` 0 so the gated MLP is the whole
    feed-forward): mamba_n_heads, mamba_d_head, mamba_d_state, mamba_d_conv,
    mamba_chunk_size, mamba_expand, mamba_n_groups, shared_intermediate_size,
    attention_multiplier, embedding_multiplier, residual_multiplier,
    logits_scaling, position_embedding_type, tie_word_embeddings

    latent attention, sparse after leading dense layers, multi-token
    prediction (JoyAI-LLM-Flash; DeepSeek-V3's key set, no ``layer_types``:
    every layer is ``latent_attention``; told by ``kv_lora_rank``):
    q_lora_rank, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
    v_head_dim, rope_theta, rope_scaling (null), rope_interleave,
    first_k_dense_replace, moe_layer_freq (1), intermediate_size,
    moe_intermediate_size, n_routed_experts, n_shared_experts,
    num_experts_per_tok, scoring_func (sigmoid | softmax), topk_method
    (noaux_tc: the selection bias; greedy: none), n_group and topk_group (1),
    norm_topk_prob, routed_scaling_factor, num_nextn_predict_layers (0 | 1),
    tie_word_embeddings (false)

    state-space / attention / routed-experts hybrid whose every layer is ONE
    mixer (NVIDIA-Nemotron-3-Nano-30B-A3B; ``nemotron_h``'s key set, told by
    ``hybrid_override_pattern``: one letter a layer, ``M`` a Mamba-2 mixer,
    ``*`` attention without positional encoding, ``E`` routed experts; ``-``,
    a dense relu2 MLP layer, is refused): hybrid_override_pattern,
    mamba_num_heads, mamba_head_dim, ssm_state_size, n_groups (the scan's B/C
    groups), conv_kernel, chunk_size, use_conv_bias (true), mamba_proj_bias
    (false), mlp_hidden_act (relu2: ungated experts of two matrices),
    moe_intermediate_size, moe_shared_expert_intermediate_size,
    n_routed_experts, n_shared_experts, num_experts_per_tok, norm_topk_prob,
    routed_scaling_factor, n_group and topk_group (1: the router's, no
    group-limited routing), layer_norm_epsilon, tie_word_embeddings (false);
    sigmoid scores with a selection bias, as the third family's

    gated delta-rule linear attention beside full attention without
    positional encoding, under post-norm blocks (Olmo-Hybrid-7B; Qwen3-Next's
    ``linear_*`` keys beside Olmo 3's, told by ``linear_num_key_heads``;
    ``layer_types`` of ``linear_attention`` and ``full_attention``):
    linear_num_key_heads and linear_num_value_heads (equal: one value head
    a key head), linear_key_head_dim, linear_value_head_dim,
    linear_conv_kernel_dim, linear_allow_neg_eigval (beta = 2 sigmoid where
    true), intermediate_size (one dense gated MLP a layer), rope_parameters
    with rope_theta null (no rotary), hidden_act (silu), attention_bias
    (false), tie_word_embeddings (false). The blocks are Olmo 2's reordered
    norm (``h = x + RMSNorm(mixer(x))``) and the full layers' q/k norm is
    over the whole projection

and five of this repo's own: ``experts_held`` (ids of the experts this chip
holds, default all: one chip's share of an expert-parallel layer),
``first_layer`` (the fourth family's: the layer of the pattern that the
``num_hidden_layers`` built start at, default 0: one pipeline stage),
``router_width`` (the fourth family's: the router's width where
``n_routed_experts`` counts the experts held), ``initializer_range`` (default
0.02) and, for the third and fourth families, ``router_bias_update_rate``
(the speed of the selection bias, default 0.001: DeepSeek-V3's,
arXiv:2412.19437 section 4.2). ``layer_types`` may be longer
than ``num_hidden_layers``: the first that many are built. ``head_dim``
defaults to ``hidden_size / num_attention_heads``. A model with
``num_nextn_predict_layers`` 1 returns ``Table(logits, logits_1)`` and
trains under ``nn.MultiTokenCrossEntropyCriterion``.

``decoder_lm_reference`` (attention, gated or not, + routed experts or a
dense layer),
``hybrid_lm_reference`` (state-space + attention, dense MLP),
``delta_hybrid_lm_reference`` (gated delta rule + attention, post-norm),
``latent_moe_lm_reference`` (latent attention, dense and sparse layers, the
multi-token-prediction module) and ``nemotron_h_lm_reference`` (one mixer a
layer: grouped state-space scan, attention, relu2 experts) are the plain
float32 references of the same equations; ``reference_config`` and
``reference_params`` hand the one that
fits this model's sizes and parameters.
"""

from __future__ import annotations

from typing import Dict

from .. import nn


def is_latent(config: Dict) -> bool:
    """Whether ``config`` is of the latent-attention family."""
    return "kv_lora_rank" in config


def is_one_mixer(config: Dict) -> bool:
    """Whether ``config`` is of the family whose every layer is one mixer."""
    return "hybrid_override_pattern" in config


def is_delta_hybrid(config: Dict) -> bool:
    """Whether ``config`` is of the gated delta-rule hybrid family."""
    return "linear_num_key_heads" in config


# the delta-rule family's layer kinds
DELTA_KINDS = ("linear_attention", "full_attention")


# hybrid_override_pattern's letters; "-" (a dense relu2 MLP layer) is refused
PATTERN_KINDS = {"M": "mamba", "*": "attention", "E": "experts"}


def layer_types(config: Dict):
    if is_latent(config):
        return ["latent_attention"] * int(config["num_hidden_layers"])
    if is_one_mixer(config):
        first, n = int(config.get("first_layer", 0)), int(
            config["num_hidden_layers"])
        pattern = config["hybrid_override_pattern"][first:first + n]
        bad = sorted(set(pattern) - set(PATTERN_KINDS))
        if bad or len(pattern) != n:
            raise ValueError(
                f"decoder_lm.from_config: hybrid_override_pattern {pattern!r} "
                f"from layer {first}; accepts one of {sorted(PATTERN_KINDS)} "
                f"for each of num_hidden_layers {config['num_hidden_layers']} "
                "layers ('-', a dense MLP layer, is not built)")
        return [PATTERN_KINDS[letter] for letter in pattern]
    kinds = list(config["layer_types"])[:int(config["num_hidden_layers"])]
    if len(kinds) != int(config["num_hidden_layers"]):
        raise ValueError(
            f"layer_types names {len(kinds)} layers, num_hidden_layers is "
            f"{config['num_hidden_layers']}")
    return kinds


def router_width(config: Dict) -> int:
    if is_one_mixer(config):
        return int(config.get("router_width", config["n_routed_experts"]))
    return int(config["n_routed_experts" if is_latent(config)
                      else "num_experts"])


def experts_held(config: Dict):
    return tuple(config.get("experts_held", range(router_width(config))))


# of the first family's mlp_layer_types (first_family_mlp_kinds)
CONFIG_MLP_KINDS = ("dense", "sparse")
# the attention output gates built: gating's value, gating_types' entries
GATINGS, GATING_TYPES = ("per-head",), ("per_head",)


def is_hybrid(config: Dict) -> bool:
    """Whether ``config`` is of the state-space hybrid family."""
    return "mamba" in layer_types(config)


def head_dim(config: Dict) -> int:
    return int(config.get("head_dim") or int(config["hidden_size"])
               // int(config["num_attention_heads"]))


def _mamba(config: Dict) -> Dict:
    """``nn.Mamba2Mixer``'s sizes from the ``mamba_*`` keys."""
    heads, dim = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    if heads * dim != int(config["mamba_expand"]) * int(config["hidden_size"]):
        raise ValueError(
            f"decoder_lm.from_config: {heads} mamba heads of {dim} are not "
            f"mamba_expand {config['mamba_expand']} x hidden_size")
    if int(config.get("mamba_n_groups", 1)) != 1:
        raise ValueError("decoder_lm.from_config: accepts mamba_n_groups 1 "
                         "(one B/C group shared by all heads), got "
                         f"{config['mamba_n_groups']}")
    if not config.get("mamba_conv_bias", True) or config.get("mamba_proj_bias"):
        raise ValueError("decoder_lm.from_config: accepts mamba_conv_bias "
                         "true and mamba_proj_bias false")
    return dict(heads=heads, head_dim=dim, state=int(config["mamba_d_state"]),
                conv=int(config["mamba_d_conv"]),
                chunk=int(config["mamba_chunk_size"]))


def _accepts(config: Dict, key: str, accepted, default=None):
    value = config.get(key, default)
    if value not in accepted:
        raise ValueError(f"decoder_lm.from_config: {key} {value!r}; accepts "
                         f"{' or '.join(repr(a) for a in accepted)}")
    return value


def bias_update_rate(config: Dict) -> float:
    """The speed of the selection bias (the latent and one-mixer families)."""
    return float(config.get("router_bias_update_rate", 1e-3))


def mlp_layer_types(config: Dict):
    """The latent family's feed-forward, layer by layer."""
    dense = int(config.get("first_k_dense_replace", 0))
    return ["dense" if i < dense else "sparse"
            for i in range(int(config["num_hidden_layers"]))]


def _latent(config: Dict) -> nn.DecoderLM:
    """DeepSeek-V3's key set: latent attention in every layer, leading dense
    layers, then sparse ones with a shared expert, an MTP module."""
    _accepts(config, "rope_scaling", (None,))
    _accepts(config, "n_group", (1,), 1)
    _accepts(config, "topk_group", (1,), 1)
    _accepts(config, "moe_layer_freq", (1,), 1)
    _accepts(config, "tie_word_embeddings", (False,), False)
    _accepts(config, "attention_bias", (False,), False)
    scoring = _accepts(config, "scoring_func", ("sigmoid", "softmax"))
    method = _accepts(config, "topk_method", ("noaux_tc", "greedy"))
    mtp = _accepts(config, "num_nextn_predict_layers", (0, 1), 0)
    if not isinstance(config.get("q_lora_rank"), int):
        raise ValueError("decoder_lm.from_config: q_lora_rank "
                         f"{config.get('q_lora_rank')!r}; accepts an integer "
                         "(the low-rank query path)")
    if scoring == "softmax":
        if method != "greedy" or float(config["routed_scaling_factor"]) != 1.0 \
                or int(config.get("n_shared_experts", 0)):
            raise ValueError(
                "decoder_lm.from_config: scoring_func 'softmax' accepts "
                "topk_method 'greedy', routed_scaling_factor 1 and no shared "
                "expert; 'sigmoid' takes the others")
        router = {}
    else:
        router = dict(
            scoring="sigmoid",
            routed_scaling=float(config["routed_scaling_factor"]),
            bias_update_rate=bias_update_rate(config)
            if method == "noaux_tc" else None,
            shared_size=int(config["moe_intermediate_size"])
            * int(config.get("n_shared_experts", 0)))
    if not config.get("norm_topk_prob", True):
        raise ValueError("decoder_lm.from_config: the router's chosen "
                         "weights are renormalised (norm_topk_prob)")
    nope, rope = (int(config["qk_nope_head_dim"]),
                  int(config["qk_rope_head_dim"]))
    return nn.DecoderLM(
        vocab_size=int(config["vocab_size"]),
        hidden_size=int(config["hidden_size"]),
        layer_types=layer_types(config),
        num_heads=int(config["num_attention_heads"]),
        num_kv_heads=int(config["num_attention_heads"]),
        head_dim=nope + rope,
        eps=float(config["rms_norm_eps"]),
        init_std=float(config.get("initializer_range", 0.02)),
        rope_parameters={"latent_attention": {
            "rope_type": "default", "rope_theta": config["rope_theta"]}},
        latent=dict(q_rank=int(config["q_lora_rank"]),
                    kv_rank=int(config["kv_lora_rank"]), nope_dim=nope,
                    rope_dim=rope, v_dim=int(config["v_head_dim"]),
                    interleaved=bool(config.get("rope_interleave", False))),
        mlp_layer_types=mlp_layer_types(config),
        mlp_size=int(config["intermediate_size"]),
        n_experts=int(config["n_routed_experts"]),
        experts_per_token=int(config["num_experts_per_tok"]),
        expert_size=int(config["moe_intermediate_size"]),
        experts_held=experts_held(config), router=router, mtp_modules=mtp)


def _one_mixer(config: Dict) -> nn.DecoderLM:
    """``nemotron_h``'s key set: every layer one mixer behind one norm."""
    _accepts(config, "n_group", (1,), 1)
    _accepts(config, "topk_group", (1,), 1)
    _accepts(config, "mamba_proj_bias", (False,), False)
    _accepts(config, "use_conv_bias", (True,), True)
    _accepts(config, "use_bias", (False,), False)
    _accepts(config, "attention_bias", (False,), False)
    _accepts(config, "mlp_bias", (False,), False)
    _accepts(config, "mlp_hidden_act", ("relu2",))
    _accepts(config, "mamba_hidden_act", ("silu",), "silu")
    _accepts(config, "norm_topk_prob", (True,), True)
    _accepts(config, "tie_word_embeddings", (False,), False)
    return nn.DecoderLM(
        vocab_size=int(config["vocab_size"]),
        hidden_size=int(config["hidden_size"]),
        layer_types=layer_types(config),
        mlp_layer_types=["none"] * int(config["num_hidden_layers"]),
        num_heads=int(config["num_attention_heads"]),
        num_kv_heads=int(config["num_key_value_heads"]),
        head_dim=head_dim(config),
        eps=float(config["layer_norm_epsilon"]),
        init_std=float(config.get("initializer_range", 0.02)),
        qk_norm=False,       # and no rope_parameters: no positional encoding
        mamba=dict(heads=int(config["mamba_num_heads"]),
                   head_dim=int(config["mamba_head_dim"]),
                   state=int(config["ssm_state_size"]),
                   groups=int(config["n_groups"]),
                   conv=int(config["conv_kernel"]),
                   chunk=int(config["chunk_size"])),
        n_experts=router_width(config),
        experts_per_token=int(config["num_experts_per_tok"]),
        expert_size=int(config["moe_intermediate_size"]),
        experts_held=experts_held(config),
        router=dict(
            form="relu2", scoring="sigmoid",
            routed_scaling=float(config["routed_scaling_factor"]),
            bias_update_rate=bias_update_rate(config),
            shared_size=int(config["moe_shared_expert_intermediate_size"])
            * int(config.get("n_shared_experts", 0))))


def _delta(config: Dict) -> Dict:
    """``nn.GatedDeltaNet``'s arguments from the ``linear_*`` keys."""
    keys, values = (int(config["linear_num_key_heads"]),
                    int(config["linear_num_value_heads"]))
    if keys != values:
        raise ValueError(
            f"decoder_lm.from_config: linear_num_key_heads {keys} and "
            f"linear_num_value_heads {values}; accepts equal counts (one "
            "value head a key head)")
    return dict(heads=keys, key_dim=int(config["linear_key_head_dim"]),
                value_dim=int(config["linear_value_head_dim"]),
                conv=int(config["linear_conv_kernel_dim"]),
                neg_eigval=bool(config["linear_allow_neg_eigval"]))


def _delta_hybrid(config: Dict) -> nn.DecoderLM:
    """Olmo-Hybrid's key set: Gated DeltaNet and full-attention layers
    without positional encoding, each with one gated MLP, under post-norm
    blocks."""
    _accepts(config, "tie_word_embeddings", (False,), False)
    _accepts(config, "attention_bias", (False,), False)
    _accepts(config, "hidden_act", ("silu",), "silu")
    rope = config.get("rope_parameters") or {}
    if rope.get("rope_theta") is not None or config.get("rope_theta") \
            is not None:
        raise ValueError("decoder_lm.from_config: rope_theta "
                         f"{rope.get('rope_theta', config.get('rope_theta'))!r}; "
                         "accepts null (no rotary beside linear_attention "
                         "layers)")
    kinds = layer_types(config)
    bad = sorted(set(kinds) - set(DELTA_KINDS))
    if bad:
        raise ValueError(f"decoder_lm.from_config: layer_types {bad}; accepts "
                         f"{DELTA_KINDS} beside linear_num_key_heads")
    if "mlp_layer_types" in config or int(config.get("num_local_experts", 0)):
        raise ValueError("decoder_lm.from_config: a delta-rule hybrid takes "
                         "one dense gated MLP a layer (no mlp_layer_types, "
                         "no experts)")
    return nn.DecoderLM(
        vocab_size=int(config["vocab_size"]),
        hidden_size=int(config["hidden_size"]),
        layer_types=kinds,
        num_heads=int(config["num_attention_heads"]),
        num_kv_heads=int(config["num_key_value_heads"]),
        head_dim=head_dim(config),
        eps=float(config["rms_norm_eps"]),
        init_std=float(config.get("initializer_range", 0.02)),
        mlp_size=int(config["intermediate_size"]),
        qk_norm="whole",     # and no rope_parameters: no positional encoding
        delta=_delta(config), post_norm=True)


def first_family_mlp_kinds(config: Dict):
    """The first family's feed-forward, layer by layer: Qwen3-MoE's rule (a
    layer is sparse unless ``mlp_only_layers`` names it or ``(i + 1) %
    decoder_sparse_step`` is not 0), which ``mlp_layer_types``, where given,
    has to state as well."""
    n = int(config["num_hidden_layers"])
    only = set(config.get("mlp_only_layers") or ())
    step = int(config.get("decoder_sparse_step", 1))
    kinds = ["sparse" if i not in only and (i + 1) % step == 0 else "dense"
             for i in range(n)]
    given = list(config.get("mlp_layer_types", kinds))[:n]
    bad = sorted(set(given) - set(CONFIG_MLP_KINDS))
    if bad:
        raise ValueError(f"decoder_lm.from_config: mlp_layer_types {bad}; "
                         f"accepts {CONFIG_MLP_KINDS}, or no such key and "
                         "num_local_experts 0 for one dense gated MLP a layer")
    if given != kinds:
        raise ValueError(
            f"decoder_lm.from_config: mlp_layer_types {given} is not what "
            f"mlp_only_layers {sorted(only)} and decoder_sparse_step {step} "
            f"give ({kinds})")
    return kinds


def attention_gated(config: Dict) -> bool:
    """Whether the first family's attention layers gate their heads' outputs
    (``gating`` ``per-head``, every ``gating_types`` entry ``per_head``)."""
    gating = _accepts(config, "gating", (None,) + GATINGS)
    types = list(config.get("gating_types") or ())[
        :int(config["num_hidden_layers"])]
    if types and (gating is None or set(types) - set(GATING_TYPES)):
        raise ValueError(
            f"decoder_lm.from_config: gating {gating!r}, gating_types "
            f"{sorted(set(types))}; accepts gating {GATINGS[0]!r} with "
            f"gating_types {GATING_TYPES[0]!r} (one sigmoid a head), or "
            "neither")
    return gating is not None


def layer_heads(config: Dict):
    """Query heads layer by layer (``num_attention_heads_per_layer``, or
    ``num_attention_heads`` in every layer)."""
    n = int(config["num_hidden_layers"])
    heads = config.get("num_attention_heads_per_layer")
    if heads is None:
        return [int(config["num_attention_heads"])] * n
    if len(heads) < n:
        raise ValueError(f"decoder_lm.from_config: num_attention_heads_per_"
                         f"layer names {len(heads)} layers, num_hidden_layers "
                         f"is {n}")
    return [int(h) for h in heads[:n]]


def _first_family_moe(config: Dict) -> Dict:
    """The router's further arguments: the routed sum's scale and a shared
    expert behind its sigmoid gate (Qwen2-MoE's and Qwen3-Next's, whose keys
    these are)."""
    _accepts(config, "moe_router_logit_softcapping", (0, None), 0)
    _accepts(config, "moe_apply_router_weight_on_input", (False,), False)
    router = {}
    scaling = float(config.get("moe_routed_scaling_factor", 1.0))
    if scaling != 1.0:
        router["routed_scaling"] = scaling
    shared = int(config.get("shared_expert_intermediate_size", 0))
    if shared:
        router.update(shared_size=shared, shared_gate=True)
    return router


def from_config(config: Dict) -> nn.DecoderLM:
    """The ``nn.DecoderLM`` that ``config`` describes (not yet built: the
    optimizer builds it from the first batch, or call ``build``)."""
    if is_latent(config):
        return _latent(config)
    if is_one_mixer(config):
        return _one_mixer(config)
    if is_delta_hybrid(config):
        return _delta_hybrid(config)
    kinds = layer_types(config)
    # a linear_attention layer is the delta-rule family's, told by its keys
    accepted = tuple(k for k in nn.decoder.LAYER_KINDS
                     if k != "linear_attention")
    bad = sorted(set(kinds) - set(accepted))
    if bad:
        raise ValueError(f"decoder_lm.from_config: layer_types {bad}; accepts "
                         f"{accepted} (linear_attention beside "
                         "linear_num_key_heads)")
    common = dict(
        vocab_size=int(config["vocab_size"]),
        hidden_size=int(config["hidden_size"]),
        layer_types=kinds,
        num_heads=int(config["num_attention_heads"]),
        num_kv_heads=int(config["num_key_value_heads"]),
        head_dim=head_dim(config),
        eps=float(config["rms_norm_eps"]),
        init_std=float(config.get("initializer_range", 0.02)),
    )
    if is_hybrid(config):
        if "mlp_layer_types" in config:
            raise ValueError(
                f"decoder_lm.from_config: mlp_layer_types "
                f"{config['mlp_layer_types']}; a state-space hybrid takes no "
                "such key (one dense gated MLP a layer)")
        if int(config.get("num_local_experts", 0)):
            raise ValueError("decoder_lm.from_config: a state-space hybrid "
                             "with routed experts (num_local_experts > 0) is "
                             "not built; accepts 0")
        if config.get("position_embedding_type", "nope") != "nope":
            raise ValueError("decoder_lm.from_config: position_embedding_type "
                             f"{config['position_embedding_type']!r}; accepts "
                             "'nope' beside mamba layers")
        return nn.DecoderLM(
            **common,
            mlp_size=int(config["shared_intermediate_size"]),
            qk_norm=False,
            attention_scale=float(config["attention_multiplier"]),
            mamba=_mamba(config),
            embedding_multiplier=float(config["embedding_multiplier"]),
            residual_multiplier=float(config["residual_multiplier"]),
            logits_divisor=float(config["logits_scaling"]),
            tie_embeddings=bool(config["tie_word_embeddings"]))
    if not config.get("norm_topk_prob", True):
        raise ValueError("decoder_lm.from_config: the router's chosen "
                         "probabilities are renormalised (norm_topk_prob)")
    if config.get("partial_rotary_factor", 1) != 1:
        raise ValueError("decoder_lm.from_config: partial_rotary_factor is "
                         "read from each rope_parameters entry")
    mlp_kinds = first_family_mlp_kinds(config)
    return nn.DecoderLM(
        **common,
        sliding_window=int(config["sliding_window"]),
        rope_parameters=config["rope_parameters"],
        n_experts=int(config["num_experts"]),
        experts_per_token=int(config["num_experts_per_tok"]),
        expert_size=int(config["moe_intermediate_size"]),
        experts_held=experts_held(config),
        mlp_layer_types=mlp_kinds,
        mlp_size=int(config["intermediate_size"]) if "dense" in mlp_kinds
        else 0,
        layer_heads=layer_heads(config),
        attention_gate=attention_gated(config),
        router=_first_family_moe(config),
    )


def reference_config(config: Dict) -> Dict:
    """What the family's reference reads, from the same dict."""
    if is_delta_hybrid(config):
        keys = ("num_attention_heads", "num_key_value_heads", "rms_norm_eps",
                "linear_num_key_heads", "linear_key_head_dim",
                "linear_value_head_dim", "linear_allow_neg_eigval")
        out = {k: config[k] for k in keys}
        out["head_dim"] = head_dim(config)
        out["layer_types"] = layer_types(config)
        return out
    if is_one_mixer(config):
        keys = ("num_attention_heads", "num_key_value_heads",
                "layer_norm_epsilon", "mamba_num_heads", "mamba_head_dim",
                "ssm_state_size", "n_groups", "chunk_size",
                "num_experts_per_tok", "routed_scaling_factor")
        out = {k: config[k] for k in keys}
        out["head_dim"] = head_dim(config)
        out["layer_types"] = layer_types(config)
        out["experts_held"] = experts_held(config)
        out["bias_update_rate"] = bias_update_rate(config)
        return out
    if is_latent(config):
        keys = ("num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "rope_theta", "rope_interleave",
                "rms_norm_eps", "num_experts_per_tok", "routed_scaling_factor")
        out = {k: config[k] for k in keys}
        out["experts_held"] = experts_held(config)
        out["bias_update_rate"] = bias_update_rate(config)
        return out
    if is_hybrid(config):
        keys = ("num_attention_heads", "num_key_value_heads", "rms_norm_eps",
                "mamba_n_heads", "mamba_d_head", "mamba_d_state",
                "mamba_chunk_size", "attention_multiplier",
                "embedding_multiplier", "residual_multiplier",
                "logits_scaling")
        out = {k: config[k] for k in keys}
        out["head_dim"] = head_dim(config)
        out["layer_types"] = layer_types(config)
        return out
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "sliding_window", "rope_parameters", "rms_norm_eps",
            "num_experts_per_tok")
    out = {k: config[k] for k in keys}
    out["layer_types"] = layer_types(config)
    out["experts_held"] = experts_held(config)
    out["layer_heads"] = layer_heads(config)
    out["mlp_layer_types"] = first_family_mlp_kinds(config)
    out["attention_gate"] = attention_gated(config)
    moe = _first_family_moe(config)
    out["routed_scaling"] = moe.get("routed_scaling", 1.0)
    out["shared_expert_gate"] = moe.get("shared_gate", False)
    return out


def _reference_layer(block: Dict) -> Dict:
    out = {name: block[name]["weight"]   # a one-mixer block has the one norm
           for name in ("ln", "ln1", "ln2") if name in block}
    for name in ("attn", "ssm", "delta", "experts", "mlp"):   # leaves as they are
        out.update(block.get(name, {}))
    return out


def reference_biases(state: Dict) -> list:
    """A built ``DecoderLM``'s router biases (from its state tree) in the
    order of ``latent_moe_lm_reference`` and ``nemotron_h_lm_reference``: one
    for each routed layer, the MTP module's last."""
    names = sorted((k for k in state if k.startswith("layer_")),
                   key=lambda k: int(k.split("_")[1]))
    blocks = [state[n]["block"] for n in names]
    if "mtp" in state:
        blocks.append(state["mtp"]["layer"]["block"])
    return [b["experts"]["selection_bias"] for b in blocks
            if "selection_bias" in b.get("experts", {})]


def reference_params(params: Dict) -> Dict:
    """A built ``DecoderLM``'s parameter (or gradient) tree in the layout of
    its reference; the leaves are the same arrays. A tied model has no
    ``head``, one without an MTP module no ``mtp``."""
    names = sorted((k for k in params if k.startswith("layer_")),
                   key=lambda k: int(k.split("_")[1]))
    out = {"embed": params["embed"]["weight"],
           "layers": [_reference_layer(params[n]["block"])  # inside nn.Remat
                      for n in names],
           "final_norm": params["final_norm"]["weight"]}
    if params["head"]:
        out["head"] = params["head"]["weight"]
    if "mtp" in params:
        mtp = params["mtp"]
        out["mtp"] = {
            "enorm": mtp["enorm"]["weight"], "hnorm": mtp["hnorm"]["weight"],
            "eh_proj": mtp["eh_proj"]["weight"], "norm": mtp["norm"]["weight"],
            "layer": _reference_layer(mtp["layer"]["block"])}
    return out
