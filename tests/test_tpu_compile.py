"""Compile the device path for a described TPU v5e, with no chip attached.

Interpret mode runs the Pallas kernels as plain jax ops, so it accepts code
the TPU's Mosaic compiler refuses.  These tests compile the hash-partition
kernels and both fused ShufflePlans at real sizes for one v5e chip and check
that each lowers to a TPU custom call.  The topology is described only
inside a fixture: one process at a time may load the TPU library.

The last tests cover the chip entry points' own guards: ``chip_smoke.py``
refuses to run without a TPU, and the persistent compile cache lands in its
fixed directory.
"""

import importlib.util
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.data import device_repartition as dr
from repro.kernels.hash_partition.ops import (padded_partition_ids,
                                              partition_ids,
                                              scatter_permutation)
from repro.runtime.compile_cache import DEFAULT_DIR, ENV_VAR, \
    enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]
KERNEL_ROWS = 1 << 20
PLAN_BUCKET = 1 << 23
PLAN_WORKERS = 32
PACKS = (("float32", 2), ("int32", 3))


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A single-chip sharding on the described topology, with the persistent
    compile cache off: entries compiled for an absent chip cannot be read
    back."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("m", [32, 256])
@pytest.mark.parametrize("kernel", ["hash_partition", "hash_partition_padded",
                                    "scatter_perm"])
def test_kernel_compiles_for_v5e(one_chip, kernel, m):
    keys = _spec(one_chip, (KERNEL_ROWS,), jnp.int32)
    if kernel == "hash_partition":
        lowered = partition_ids.lower(keys, m, interpret=False,
                                      use_kernel=True)
    elif kernel == "hash_partition_padded":
        lowered = padded_partition_ids.lower(
            keys, _spec(one_chip, (), jnp.int32), m, interpret=False,
            use_kernel=True)
    else:
        lowered = scatter_permutation.lower(
            keys, _spec(one_chip, (m,), jnp.int32), interpret=False,
            use_kernel=True)
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("kind", ["rebucket", "scatter"])
def test_fused_plan_compiles_for_v5e(one_chip, kind):
    B, m = PLAN_BUCKET, PLAN_WORKERS
    packs = tuple(_spec(one_chip, (B, width), jnp.dtype(dt))
                  for dt, width in PACKS)
    i32 = jnp.int32
    try:
        if kind == "rebucket":
            plan = dr._fused_rebucket_plan(m, B, PACKS, False, True)
            lowered = plan.fn.lower(_spec(one_chip, (B,), i32),
                                    _spec(one_chip, (), i32), packs)
        else:
            plan = dr._fused_scatter_plan(m, B, B, PACKS, False, True)
            lowered = plan.fn.lower(
                _spec(one_chip, (B,), i32), _spec(one_chip, (m,), i32),
                _spec(one_chip, (), i32), _spec(one_chip, (m,), i32), packs)
        assert "tpu_custom_call" in lowered.compile().as_text()
    finally:
        dr.clear_plan_cache()


# -- entry-point guards --------------------------------------------------------

def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_without_tpu():
    smoke = _load_chip_smoke()
    with pytest.raises(SystemExit) as exc:
        smoke.require_tpu()
    assert "no TPU found" in str(exc.value.code)


def test_compile_cache_uses_env_dir_and_sets_nothing(monkeypatch, tmp_path):
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv(ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
