"""The FLOPs walk and the table of peaks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import flops
from benchmark.lib.peaks import peaks


def test_dot_general_by_hand():
    a = jax.ShapeDtypeStruct((8, 32), jnp.bfloat16)
    b = jax.ShapeDtypeStruct((32, 16), jnp.float32)
    (c,) = flops.matmul_costs(lambda a, b: jnp.dot(a.astype(jnp.float32), b), a, b)
    assert c.primitive == "dot_general"
    assert c.flops == 2 * 8 * 16 * 32
    assert c.bytes == (8 * 32 + 32 * 16 + 8 * 16) * 4  # operands as the dot sees them


def test_conv_by_hand():
    x = jax.ShapeDtypeStruct((4, 6, 10, 10), jnp.bfloat16)   # NCHW
    w = jax.ShapeDtypeStruct((12, 6, 3, 3), jnp.bfloat16)    # OIHW

    def conv(x, w):
        return jax.lax.conv_general_dilated(x, w, (2, 2), "VALID")

    (c,) = flops.matmul_costs(conv, x, w)
    out = 4 * 12 * 4 * 4                                     # (10-3)//2+1 = 4
    assert c.flops == 2 * out * 6 * 3 * 3
    assert c.bytes == (4 * 6 * 10 * 10 + 12 * 6 * 3 * 3 + out) * 2


def test_grouped_conv_counts_its_group_only():
    x = jax.ShapeDtypeStruct((1, 8, 5, 5), jnp.float32)
    w = jax.ShapeDtypeStruct((8, 2, 3, 3), jnp.float32)      # 4 groups of 2

    def conv(x, w):
        return jax.lax.conv_general_dilated(x, w, (1, 1), "SAME",
                                            feature_group_count=4)

    (c,) = flops.matmul_costs(conv, x, w)
    assert c.flops == 2 * (8 * 5 * 5) * (2 * 3 * 3)


def test_walk_enters_sub_jaxprs():
    a = jax.ShapeDtypeStruct((4, 4), jnp.float32)
    inner = jax.jit(lambda a: a @ a)
    costs = flops.matmul_costs(lambda a: jax.checkpoint(inner)(a) @ a, a)
    assert [c.flops for c in costs] == [2 * 4 * 4 * 4] * 2


def test_resnet50_forward_is_he_et_al_table_1():
    from bigdl_tpu.models import ResNet

    model = ResNet(50, class_num=1000, dataset="imagenet", with_log_softmax=True)
    params, state = model.init(sample_input=np.zeros((1, 3, 224, 224), np.float32))
    x = jax.ShapeDtypeStruct((2, 3, 224, 224), jnp.float32)
    key = jax.random.PRNGKey(0)
    costs = flops.matmul_costs(
        lambda p, s, x: model.apply(p, s, x, training=True, rng=key),
        params, state, x)
    per_record = sum(c.flops for c in costs) / 2
    # 4.1 G multiply-adds (He et al., Table 1); TRACE_ANALYSIS_r3's 3.12 TFLOP
    # a step at b128 is this x 3 x 128
    assert per_record == pytest.approx(2 * 4.1e9, rel=0.03)
    assert per_record * 3 * 128 == pytest.approx(3.12e12, rel=0.03)


def test_least_seconds_says_which_bound():
    big = flops.OpCost("dot_general", 197e12, 1.0)
    wide = flops.OpCost("dot_general", 1.0, 819e9)
    assert flops.least_seconds([big], 197e12, 819e9) == (pytest.approx(1.0), "compute")
    assert flops.least_seconds([wide], 197e12, 819e9) == (pytest.approx(1.0), "memory")


def test_unknown_device_kind_raises():
    assert peaks("TPU v5 lite").flops_per_s == 197e12
    with pytest.raises(KeyError, match="TPU v9"):
        peaks("TPU v9")
