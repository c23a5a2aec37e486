"""Decoder-only language models from a catalog-style config dict, so that the
next one is a dict and not a class.

``decoder_lm.from_config(config)`` reads the keys a model's public ``config.json`` uses.
Four families so far, told apart by their own keys (the cuts to one chip are
``benchmark/configs/mellum2_12b.json``, ``granite_4_0_h_micro.json``,
``joyai_llm_flash.json`` and ``nemotron_3_nano_30b_a3b.json``)::

    vocab_size, hidden_size, num_hidden_layers, layer_types,
    num_attention_heads, num_key_value_heads, head_dim, rms_norm_eps

    sparse, windowed (Mellum2-12B-A2.5B-Instruct):
    sliding_window, rope_parameters, num_experts, num_experts_per_tok,
    moe_intermediate_size, norm_topk_prob, mlp_layer_types

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

``decoder_lm_reference`` (attention + routed experts),
``hybrid_lm_reference`` (state-space + attention, dense MLP),
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


# of a config's mlp_layer_types; no such key: one dense gated MLP a layer
CONFIG_MLP_KINDS = ("sparse",)


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


def from_config(config: Dict) -> nn.DecoderLM:
    """The ``nn.DecoderLM`` that ``config`` describes (not yet built: the
    optimizer builds it from the first batch, or call ``build``)."""
    if is_latent(config):
        return _latent(config)
    if is_one_mixer(config):
        return _one_mixer(config)
    kinds = layer_types(config)
    bad = sorted(set(kinds) - set(nn.decoder.LAYER_KINDS))
    if bad:
        raise ValueError(f"decoder_lm.from_config: layer_types {bad}; accepts "
                         f"{nn.decoder.LAYER_KINDS}")
    bad = sorted(set(config.get("mlp_layer_types", [])[:len(kinds)])
                 - set(CONFIG_MLP_KINDS))
    if bad:
        raise ValueError(f"decoder_lm.from_config: mlp_layer_types {bad}; "
                         f"accepts {CONFIG_MLP_KINDS}, or no such key and "
                         "num_local_experts 0 for one dense gated MLP a layer")
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
    return nn.DecoderLM(
        **common,
        sliding_window=int(config["sliding_window"]),
        rope_parameters=config["rope_parameters"],
        n_experts=int(config["num_experts"]),
        experts_per_token=int(config["num_experts_per_tok"]),
        expert_size=int(config["moe_intermediate_size"]),
        experts_held=experts_held(config),
    )


def reference_config(config: Dict) -> Dict:
    """What the family's reference reads, from the same dict."""
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
    return out


def _reference_layer(block: Dict) -> Dict:
    out = {name: block[name]["weight"]   # a one-mixer block has the one norm
           for name in ("ln", "ln1", "ln2") if name in block}
    for name in ("attn", "ssm", "experts", "mlp"):   # the leaves as they are
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
