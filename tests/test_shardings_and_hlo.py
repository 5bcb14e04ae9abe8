"""Sharding-rule coverage (AbstractMesh — no devices needed) + HLO analyzer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config, list_archs
from repro.launch import hlo_analysis as H
from repro.launch import shardings, specs
from repro.core.sharding_bridge import specs_match, would_elide_collective


def _mesh(multi_pod=False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    names = ("pod", "data", "model") if multi_pod else ("data", "model")
    return AbstractMesh(shape, names)


@pytest.mark.parametrize("arch", list_archs())
@pytest.mark.parametrize("multi_pod", [False, True])
def test_param_specs_cover_and_divide(arch, multi_pod):
    """Every param leaf gets a spec; every sharded dim divides evenly."""
    cfg = get_config(arch)
    mesh = _mesh(multi_pod)
    sizes = dict(mesh.shape)
    struct = specs.params_struct(cfg)
    spec_tree = shardings.param_pspecs(cfg, struct, mesh)
    spec_tree = shardings.shard_over_dp(spec_tree, struct, mesh) \
        if cfg.param_count() >= shardings.FSDP_THRESHOLD else spec_tree

    leaves = jax.tree.leaves(struct)
    spec_leaves = jax.tree.leaves(spec_tree,
                                  is_leaf=lambda x: isinstance(x, P))
    assert len(leaves) == len(spec_leaves)
    for leaf, spec in zip(leaves, spec_leaves):
        entries = list(spec) + [None] * (len(leaf.shape) - len(spec))
        used = []
        for dim, e in zip(leaf.shape, entries):
            if e is None:
                continue
            axes = e if isinstance(e, tuple) else (e,)
            shard = 1
            for a in axes:
                assert a in sizes, f"{arch}: unknown axis {a}"
                shard *= sizes[a]
                assert a not in used, f"{arch}: axis {a} reused in {spec}"
                used.append(a)
            assert dim % shard == 0, \
                f"{arch}: dim {dim} not divisible by {shard} ({spec})"


@pytest.mark.parametrize("arch", ["qwen1.5-110b", "mamba2-370m",
                                  "deepseek-v2-236b", "whisper-small"])
def test_cache_specs_divide(arch):
    cfg = get_config(arch)
    mesh = _mesh()
    sizes = dict(mesh.shape)
    for B, L in ((128, 32768), (1, 524288)):
        struct = specs.cache_struct(cfg, B, L)
        spec_tree = shardings.cache_pspecs(cfg, struct, B, mesh)
        for leaf, spec in zip(
                jax.tree.leaves(struct),
                jax.tree.leaves(spec_tree,
                                is_leaf=lambda x: isinstance(x, P))):
            entries = list(spec) + [None] * (len(leaf.shape) - len(spec))
            for dim, e in zip(leaf.shape, entries):
                if e is None:
                    continue
                axes = e if isinstance(e, tuple) else (e,)
                shard = int(np.prod([sizes[a] for a in axes]))
                assert dim % shard == 0, f"{arch} {leaf.shape} {spec}"


def test_zero1_shards_moments_over_dp():
    cfg = get_config("gemma2-27b")
    mesh = _mesh()
    struct = specs.params_struct(cfg)
    base = shardings.param_pspecs(cfg, struct, mesh)
    z = shardings.shard_over_dp(base, struct, mesh)
    base_l = jax.tree.leaves(base, is_leaf=lambda x: isinstance(x, P))
    z_l = jax.tree.leaves(z, is_leaf=lambda x: isinstance(x, P))
    more = sum(1 for b, zz in zip(base_l, z_l) if b != zz)
    assert more > 0                       # ZeRO actually sharded something


def test_sharding_bridge_match():
    assert specs_match(P("data", None), P("data"))
    assert not specs_match(P("data", None), P(None, "data"))
    assert would_elide_collective(P("data", None), P("data", None))


# -- HLO analyzer -----------------------------------------------------------------

def test_hlo_analyzer_counts_scan_trips():
    N, G = 256, 8
    A = jax.ShapeDtypeStruct((N, N), jnp.float32)
    W = jax.ShapeDtypeStruct((G, N, N), jnp.float32)

    def f(a, ws):
        def body(h, w):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, a, ws)
        return h

    c = jax.jit(f).lower(A, W).compile()
    t = H.analyze(c.as_text())
    expect = G * 2 * N ** 3
    assert abs(t.flops - expect) / expect < 0.05
    # XLA's own cost analysis counts the body once — our analyzer must not
    ca = c.cost_analysis()
    assert t.flops > (ca.get("flops", 0) or 0) * (G - 1)


def test_hlo_analyzer_nested_scan():
    N, G1, G2 = 128, 3, 4
    A = jax.ShapeDtypeStruct((N, N), jnp.float32)
    W = jax.ShapeDtypeStruct((G2, N, N), jnp.float32)

    def f(a, ws):
        def outer(h, _):
            def inner(hh, w):
                return jnp.tanh(hh @ w), None
            h2, _ = jax.lax.scan(inner, h, ws)
            return h2, None
        h, _ = jax.lax.scan(outer, a, None, length=G1)
        return h

    c = jax.jit(f).lower(A, W).compile()
    t = H.analyze(c.as_text())
    expect = G1 * G2 * 2 * N ** 3
    assert abs(t.flops - expect) / expect < 0.05


def test_hlo_shape_bytes():
    assert H._shape_bytes("bf16[16,128]{1,0}") == 16 * 128 * 2
    assert H._shape_bytes("(f32[4,4], s32[8])") == 64 + 32
    assert H._shape_bytes("pred[7]") == 7
