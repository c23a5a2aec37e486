"""Scope names of models that came after ``lib/scopes.py``'s constant, for
readers that hand them to ``lib/scope_times.scope_ms``."""

# the latent-attention family's (bigdl_tpu/nn/decoder.py LatentAttention,
# nn/moe.py RoutedExperts' shared expert): an op belongs to the innermost of
# these that its text holds
LATENT_SCOPES = ("embed", "mla_proj", "attn_full", "moe_route", "moe_experts",
                 "moe_shared", "mlp", "lm_head")
