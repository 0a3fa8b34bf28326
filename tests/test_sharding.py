"""Sharding rules: divisibility fallbacks, serve vs train rules, batch spec.
Uses a handful of forced host devices in a subprocess-free way: these tests
only construct Meshes over the single real device via mesh abstractions, so
we test spec RESOLUTION (pure logic), not lowering."""
import numpy as np
import pytest

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro import sharding as sh
from repro.configs import get_config
from repro.models import param_shapes_and_axes


class FakeMesh:
    """Duck-typed mesh exposing .shape mapping for spec_for."""
    def __init__(self, shape):
        self.shape = shape


SINGLE = FakeMesh({"data": 16, "model": 16})
MULTI = FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_basic_tp_and_fsdp():
    spec = sh.spec_for(("embed", "ffn"), (5120, 14336), SINGLE)
    assert spec == P("data", "model")
    spec = sh.spec_for(("vocab", "embed"), (131072, 5120), SINGLE)
    assert spec == P("model", "data")


def test_indivisible_head_fallback():
    # smollm: 9 heads on a 16-way model axis -> replicated heads
    spec = sh.spec_for(("embed", "heads", "head_dim"), (576, 9, 64), SINGLE)
    assert spec == P("data")
    # stablelm 32 heads -> sharded
    spec = sh.spec_for(("embed", "heads", "head_dim"), (2560, 32, 80), SINGLE)
    assert spec == P("data", "model")


def test_expert_priority_over_ffn():
    # granite: 32 experts % 16 == 0 -> experts take the model axis
    spec = sh.spec_for(("experts", "embed", "ffn"), (32, 1024, 512), SINGLE)
    assert spec == P("model", "data")
    # mixtral: 8 experts -> ffn takes the model axis
    spec = sh.spec_for(("experts", "embed", "ffn"), (8, 6144, 16384), SINGLE)
    assert spec == P(None, "data", "model")


def test_multi_pod_fsdp_spans_pod_and_data():
    spec = sh.spec_for(("embed", "ffn"), (5120, 14336), MULTI)
    assert spec == P(("pod", "data"), "model")
    # d_model=576: 576 % 32 == 0 -> still 2-axis FSDP
    spec = sh.spec_for(("embed", "ffn"), (576, 1536), MULTI)
    assert spec == P(("pod", "data"), "model")


def test_serve_rules_keep_weights_resident_2d():
    spec = sh.spec_for(("embed", "ffn"), (6144, 16384), SINGLE,
                       sh.SERVE_RULES)
    assert spec == P(None, ("model", "data"))
    # granite expert-parallel + data-sharded ffn
    spec = sh.spec_for(("experts", "embed", "ffn"), (32, 1024, 512), SINGLE,
                       sh.SERVE_RULES)
    assert spec == P("model", None, "data")
    # odd vocab replicates
    spec = sh.spec_for(("embed", "vocab"), (2048, 92553), SINGLE,
                       sh.SERVE_RULES)
    assert spec == P()


def test_batch_spec_divisibility():
    class M(FakeMesh):
        pass
    m = FakeMesh({"pod": 2, "data": 16, "model": 16})
    assert sh.batch_spec(m, 2, shape=(256, 10)) == P(("pod", "data"), None)
    # batch=1 -> unsharded
    assert sh.batch_spec(m, 2, shape=(1, 10)) == P(None, None)
    # batch=16 -> falls back to data-only
    assert sh.batch_spec(m, 2, shape=(16, 10)) == P(("data",), None)


@pytest.mark.parametrize("name", ["mistral-nemo-12b", "mixtral-8x22b",
                                  "rwkv6-7b", "recurrentgemma-2b",
                                  "seamless-m4t-medium"])
def test_all_param_leaves_resolve(name):
    cfg = get_config(name)
    shapes, axes = param_shapes_and_axes(cfg)
    for k in shapes:
        spec = sh.spec_for(axes[k], shapes[k].shape, SINGLE)
        # every placed axis must divide the dim
        for dim, entry in zip(shapes[k].shape, tuple(spec)):
            if entry is None:
                continue
            axes_t = (entry,) if isinstance(entry, str) else entry
            n = int(np.prod([SINGLE.shape[a] for a in axes_t]))
            assert dim % n == 0, (name, k, spec)
