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
import re
import sys
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


def _compiled_plan(sharding, kind, B):
    """The optimized HLO text of one fused plan over PACKS at bucket B."""
    m, i32 = PLAN_WORKERS, jnp.int32
    packs = tuple(_spec(sharding, (B, width), jnp.dtype(dt))
                  for dt, width in PACKS)
    try:
        if kind == "rebucket":
            plan = dr._fused_rebucket_plan(m, B, PACKS, False, True)
            lowered = plan.fn.lower(_spec(sharding, (B,), i32),
                                    _spec(sharding, (), i32), packs)
        else:
            plan = dr._fused_scatter_plan(m, B, B, PACKS, False, True)
            lowered = plan.fn.lower(
                _spec(sharding, (B,), i32), _spec(sharding, (m,), i32),
                _spec(sharding, (), i32), _spec(sharding, (m,), i32), packs)
        return lowered.compile().as_text()
    finally:
        dr.clear_plan_cache()


@pytest.mark.parametrize("kind", ["rebucket", "scatter"])
def test_fused_plan_compiles_for_v5e(one_chip, kind):
    assert "tpu_custom_call" in _compiled_plan(one_chip, kind, PLAN_BUCKET)


def test_fused_scatter_plan_gathers_its_packs(one_chip):
    """The store plan builds its layout with one gather per pack: no scatter
    of wide rows, at most one 1-D int32 scatter (the inversion of each row's
    slot), and the counting-sort kernel still in place."""
    text = _compiled_plan(one_chip, "scatter", PLAN_BUCKET)
    ops = re.findall(r"= (\w+)\[([\d,]*)\]\S* (scatter|gather)\(", text)
    scatters = [(dt, dims) for dt, dims, op in ops if op == "scatter"]
    assert len(scatters) <= 1, scatters
    assert all(dt == "s32" and "," not in dims for dt, dims in scatters), \
        scatters
    row_gathers = sorted(dt for dt, dims, op in ops
                         if op == "gather" and "," in dims)
    hlo_dtype = {"float32": "f32", "int32": "s32"}
    assert row_gathers == sorted(hlo_dtype[dt] for dt, _w in PACKS), ops
    assert re.search(r"%scatter_perm(\.\d+)? = .*"
                     r'custom_call_target="tpu_custom_call"', text)


def _bench_kernels():
    """The kernel names the benchmark's trace reduction knows."""
    spec = importlib.util.spec_from_file_location(
        "bench_trace", ROOT / "bench" / "harness" / "trace.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules.setdefault(spec.name, mod)     # its dataclasses look it up
    spec.loader.exec_module(mod)
    return {n for names in mod.KERNELS.values() for n in names}


@pytest.mark.parametrize("kind, module", [("rebucket", "shuffle_rebucket"),
                                          ("scatter", "store_scatter")])
def test_fused_plan_names_its_program_and_kernels(one_chip, kind, module):
    """The trace finds the fused plans under their layer names and each
    Pallas custom call under a kernel family's name."""
    B, m = 1 << 16, PLAN_WORKERS
    packs = tuple(_spec(one_chip, (B, width), jnp.dtype(dt))
                  for dt, width in PACKS)
    i32 = jnp.int32
    try:
        if kind == "rebucket":
            plan = dr._fused_rebucket_plan(m, B, PACKS, False, True)
            text = plan.fn.lower(_spec(one_chip, (B,), i32),
                                 _spec(one_chip, (), i32),
                                 packs).compile().as_text()
        else:
            plan = dr._fused_scatter_plan(m, B, B, PACKS, False, True)
            text = plan.fn.lower(
                _spec(one_chip, (B,), i32), _spec(one_chip, (m,), i32),
                _spec(one_chip, (), i32), _spec(one_chip, (m,), i32),
                packs).compile().as_text()
    finally:
        dr.clear_plan_cache()
    assert re.search(rf"^HloModule jit_{module}\b", text, re.M)
    calls = [re.sub(r"\.\d+$", "", ln.split(" = ", 1)[0].strip().lstrip("%"))
             for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls and set(calls) <= _bench_kernels(), calls


def test_place_plan_compiles_for_v5e(one_chip):
    """The write-back's placement of rows already segmented per worker is
    one gather per pack under its own program name: no scatter and no
    kernel, so no shuffle dispatch."""
    m, i32 = PLAN_WORKERS, jnp.int32
    packs = tuple(_spec(one_chip, (PLAN_BUCKET, width), jnp.dtype(dt))
                  for dt, width in PACKS)
    try:
        plan = dr._fused_place_plan(m, PLAN_BUCKET, PLAN_BUCKET, PACKS)
        text = plan.fn.lower(_spec(one_chip, (m,), i32),
                             _spec(one_chip, (m,), i32),
                             packs).compile().as_text()
    finally:
        dr.clear_plan_cache()
    assert re.search(r"^HloModule jit_store_place\b", text, re.M)
    ops = re.findall(r"= (\w+)\[([\d,]*)\]\S* (scatter|gather)\(", text)
    assert not [op for _dt, _dims, op in ops if op == "scatter"], ops
    row_gathers = sorted(dt for dt, dims, op in ops
                         if op == "gather" and "," in dims)
    assert row_gathers == ["f32", "s32"], ops
    assert 'custom_call_target="tpu_custom_call"' not in text


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
