"""Decoder-only language models from a catalog-style config dict, so that the
next one is a dict and not a class.

``decoder_lm.from_config(config)`` reads the keys a model's public ``config.json`` uses
(the first: Mellum2-12B-A2.5B-Instruct, whose cut to one chip is
``benchmark/configs/mellum2_12b.json``)::

    vocab_size, hidden_size, num_hidden_layers, layer_types,
    num_attention_heads, num_key_value_heads, head_dim, sliding_window,
    rope_parameters, rms_norm_eps, num_experts, num_experts_per_tok,
    moe_intermediate_size, norm_topk_prob

and two of this repo's own: ``experts_held`` (ids of the experts this chip
holds, default all: one chip's share of an expert-parallel layer) and
``initializer_range`` (default 0.02). ``layer_types`` may be longer than
``num_hidden_layers``: the first that many are built.

``decoder_lm_reference`` is the plain float32 reference of the same
equations; ``reference_config`` and ``reference_params`` hand it this
model's sizes and parameters.
"""

from __future__ import annotations

from typing import Dict

from .. import nn


def layer_types(config: Dict):
    kinds = list(config["layer_types"])[:int(config["num_hidden_layers"])]
    if len(kinds) != int(config["num_hidden_layers"]):
        raise ValueError(
            f"layer_types names {len(kinds)} layers, num_hidden_layers is "
            f"{config['num_hidden_layers']}")
    return kinds


def experts_held(config: Dict):
    return tuple(config.get("experts_held", range(int(config["num_experts"]))))


def from_config(config: Dict) -> nn.DecoderLM:
    """The ``nn.DecoderLM`` that ``config`` describes (not yet built: the
    optimizer builds it from the first batch, or call ``build``)."""
    if any(t != "sparse" for t in config.get("mlp_layer_types", [])[
            :int(config["num_hidden_layers"])]):
        raise ValueError("decoder_lm.from_config: only sparse (routed-expert) MLP layers")
    if not config.get("norm_topk_prob", True):
        raise ValueError("decoder_lm.from_config: the router's chosen "
                         "probabilities are renormalised (norm_topk_prob)")
    return nn.DecoderLM(
        vocab_size=int(config["vocab_size"]),
        hidden_size=int(config["hidden_size"]),
        layer_types=layer_types(config),
        num_heads=int(config["num_attention_heads"]),
        num_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        sliding_window=int(config["sliding_window"]),
        rope_parameters=config["rope_parameters"],
        n_experts=int(config["num_experts"]),
        experts_per_token=int(config["num_experts_per_tok"]),
        expert_size=int(config["moe_intermediate_size"]),
        experts_held=experts_held(config),
        eps=float(config["rms_norm_eps"]),
        init_std=float(config.get("initializer_range", 0.02)),
    )


def reference_config(config: Dict) -> Dict:
    """What ``decoder_lm_reference`` reads, from the same dict."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "sliding_window", "rope_parameters", "rms_norm_eps",
            "num_experts_per_tok")
    out = {k: config[k] for k in keys}
    out["layer_types"] = layer_types(config)
    out["experts_held"] = experts_held(config)
    return out


def reference_params(params: Dict) -> Dict:
    """A built ``DecoderLM``'s parameter (or gradient) tree in the layout of
    ``decoder_lm_reference``; the leaves are the same arrays."""
    layers = []
    for name in sorted((k for k in params if k.startswith("layer_")),
                       key=lambda k: int(k.split("_")[1])):
        block = params[name]["block"]  # inside nn.Remat
        attn, ex = block["attn"], block["experts"]
        layers.append({
            "ln1": block["ln1"]["weight"], "ln2": block["ln2"]["weight"],
            "wq": attn["wq"], "wk": attn["wk"], "wv": attn["wv"],
            "wo": attn["wo"], "q_norm": attn["q_norm"],
            "k_norm": attn["k_norm"], "router": ex["router"],
            "w_gate": ex["w_gate"], "w_up": ex["w_up"],
            "w_down": ex["w_down"]})
    return {"embed": params["embed"]["weight"], "layers": layers,
            "final_norm": params["final_norm"]["weight"],
            "head": params["head"]["weight"]}
