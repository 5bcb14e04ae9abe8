"""Kernel microbenchmarks: oracle wall times + kernel equivalence.

On CPU the Pallas kernels run in interpret mode (Python-speed — correctness
only), so the timed path is the jnp oracle; per-shape allclose against the
kernel is asserted as part of the row.  On TPU the same harness times the
compiled kernels (`use_kernel=True`).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.hash_partition.hash_partition import hash_partition
from repro.kernels.hash_partition.ref import hash_partition_ref
from repro.kernels.ssd_scan.ref import ssd_ref
from repro.kernels.ssd_scan.ssd_scan import ssd_scan

from .common import emit


def _time(fn, *args, n=5):
    fn(*args)                      # compile
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def bench_flash():
    key = jax.random.PRNGKey(0)
    B, H, KV, S, hd = 1, 8, 2, 1024, 64
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, S, hd))
    k = jax.random.normal(ks[1], (B, KV, S, hd))
    v = jax.random.normal(ks[2], (B, KV, S, hd))
    ref = jax.jit(lambda a, b, c: attention_ref(a, b, c, causal=True))
    t = _time(ref, q, k, v)
    out_k = flash_attention(q, k, v, causal=True, block_q=256, block_k=256,
                            interpret=True)
    err = float(jnp.abs(out_k - ref(q, k, v)).max())
    flops = 4 * B * H * S * S * hd / 2
    emit("kernel_flash_attention", t * 1e6,
         f"oracle {flops / t / 1e9:.1f} GFLOP/s; kernel allclose "
         f"err={err:.1e} (interpret)")


def bench_hash_partition():
    key = jax.random.PRNGKey(1)
    n, m = 1_000_000, 256
    keys = jax.random.randint(key, (n,), 0, 2 ** 31 - 1, jnp.int32)
    ref = jax.jit(lambda x: hash_partition_ref(x, m))
    t = _time(ref, keys)
    pk, ck = hash_partition(keys[:8192], m, interpret=True)
    rk, rc = hash_partition_ref(keys[:8192], m)
    ok = bool(jnp.array_equal(pk, rk) and jnp.array_equal(ck, rc))
    emit("kernel_hash_partition", t * 1e6,
         f"oracle {n / t / 1e6:.0f} Mkeys/s over m={m}; kernel exact={ok}")


def bench_scatter_perm():
    """Counting-sort destination permutation (ISSUE 2): O(N) stable
    placement vs the O(N log N) argsort-inverse it replaces."""
    from repro.kernels.hash_partition.hash_partition import scatter_perm
    from repro.kernels.hash_partition.ref import scatter_perm_ref
    key = jax.random.PRNGKey(3)
    n, m = 1_000_000, 32
    pids = jax.random.randint(key, (n,), 0, m, jnp.int32)
    counts = jnp.bincount(pids, length=m).astype(jnp.int32)
    ref = jax.jit(scatter_perm_ref)
    t = _time(ref, pids, counts)
    dk = scatter_perm(pids[:8192],
                      jnp.bincount(pids[:8192], length=m).astype(jnp.int32),
                      interpret=True)
    dr_ = scatter_perm_ref(pids[:8192],
                           jnp.bincount(pids[:8192],
                                        length=m).astype(jnp.int32))
    ok = bool(jnp.array_equal(dk, dr_))
    emit("kernel_scatter_perm", t * 1e6,
         f"oracle (argsort-inverse) {n / t / 1e6:.0f} Mrows/s over m={m}; "
         f"counting-sort kernel exact={ok}")


def bench_ssd():
    key = jax.random.PRNGKey(2)
    B, T, H, P, N, chunk = 1, 2048, 8, 64, 128, 256
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, T, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, T, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, T, N)) * 0.3
    ref = jax.jit(lambda *a: ssd_ref(*a, chunk))
    t = _time(ref, x, dt, A, Bm, Cm)
    yk, sk = ssd_scan(x[:, :256], dt[:, :256], A, Bm[:, :256], Cm[:, :256],
                      chunk, interpret=True)
    yr, sr = ssd_ref(x[:, :256], dt[:, :256], A, Bm[:, :256], Cm[:, :256],
                     chunk)
    err = float(jnp.abs(yk - yr).max())
    emit("kernel_ssd_scan", t * 1e6,
         f"oracle {B * T * H / t / 1e6:.2f} Mtok-head/s; kernel allclose "
         f"err={err:.1e} (interpret)")


def bench_device_rebucket():
    """Host numpy re-bucket vs the jax-backed re-bucket (DESIGN §5).

    The timed device path uses the jnp oracle for pids (the Pallas kernel in
    interpret mode is Python-speed on CPU); kernel-path exactness is asserted
    on a slice, so the row certifies the full device path while timing the
    representative jnp work."""
    from repro.core.ir import _mix_hash
    from repro.data.device_repartition import device_rebucket

    rng = np.random.default_rng(3)
    n, m = 500_000, 64
    cols = {"key": rng.integers(0, 2 ** 31 - 1, n).astype(np.int64),
            "val": rng.normal(size=n).astype(np.float32)}
    keys = cols["key"]

    def host():
        pids = np.asarray(_mix_hash(keys)).astype(np.int64) % m
        order = np.argsort(pids, kind="stable")
        counts = np.bincount(pids, minlength=m)
        return {k: v[order] for k, v in cols.items()}, counts

    t0 = time.perf_counter()
    host_cols, host_counts = host()
    t_host = time.perf_counter() - t0

    device_rebucket(cols, keys, m, use_kernel=False)   # trace the plan once
    t0 = time.perf_counter()
    dev_cols, dev_counts = device_rebucket(cols, keys, m, use_kernel=False)
    t_dev = time.perf_counter() - t0

    np.testing.assert_array_equal(host_counts, dev_counts)
    np.testing.assert_array_equal(host_cols["val"], dev_cols["val"])
    k_cols, k_counts = device_rebucket(
        {k: v[:8192] for k, v in cols.items()}, keys[:8192], m,
        use_kernel=True, interpret=True)
    ok = bool(np.array_equal(
        k_cols["val"],
        device_rebucket({k: v[:8192] for k, v in cols.items()}, keys[:8192],
                        m, use_kernel=False)[0]["val"]))
    emit("kernel_device_rebucket", t_dev * 1e6,
         f"host_numpy={t_host * 1e6:.0f}us n={n} m={m} "
         f"device/host={t_dev / t_host:.2f}x kernel_exact={ok}")


def bench_scatter_skew():
    """Variable-capacity scatter (DESIGN §12): the same fused scatter plan
    writing a Zipf-skewed padded layout through a :class:`CapacityMap`
    (flat slot ranges, power-of-two buckets) vs the uniform ``(m, cap)``
    layout sized by the hottest partition."""
    from repro.data.capacity import plan_capacity_map, valid_slot_index
    from repro.data.device_repartition import (device_partition_ids,
                                               device_scatter_padded)
    from repro.data.skew import zipf_keys

    n, m = 500_000, 32
    rng = np.random.default_rng(5)
    keys = zipf_keys(n, n, 1.1, rng=rng)
    cols = {"key": keys, "val": rng.normal(size=n).astype(np.float32)}
    pids_d, hist = device_partition_ids(keys, m, use_kernel=False)
    pids = np.asarray(pids_d).astype(np.int64)
    counts = np.asarray(hist).astype(np.int64)
    cmap = plan_capacity_map(counts)
    assert cmap is not None                       # zipf keys must bucket

    def uniform():
        return device_scatter_padded(cols, pids, counts)

    def bucketed():
        return device_scatter_padded(cols, pids, counts, capacity_map=cmap)

    uniform(); bucketed()                         # trace outside the timer
    t_uni, out_u = _time(lambda: uniform()["val"], n=3), uniform()
    t_cm, out_b = _time(lambda: bucketed()["val"], n=3), bucketed()

    cap = int(counts.max())
    uni_off = np.arange(m, dtype=np.int64) * cap
    flat_u = np.asarray(out_u["val"]).reshape(-1)[
        valid_slot_index(counts, uni_off)]
    flat_b = np.asarray(out_b["val"])[valid_slot_index(counts, cmap.offsets)]
    np.testing.assert_array_equal(flat_u, flat_b)  # bit-identical rows

    slots_u, slots_b = m * cap, cmap.total_slots
    emit("kernel_scatter_skew", t_cm * 1e6,
         f"uniform={t_uni * 1e6:.0f}us n={n} m={m} zipf(1.1) "
         f"slots {slots_b} vs {slots_u} ({slots_u / slots_b:.1f}x fewer) "
         f"buckets={len(cmap.bucket_set())} bucketed/uniform="
         f"{t_cm / t_uni:.2f}x (one shared trace)")


def main():
    bench_flash()
    bench_hash_partition()
    bench_scatter_perm()
    bench_ssd()
    bench_device_rebucket()
    bench_scatter_skew()


if __name__ == "__main__":
    main()
