"""Pallas-kernel micro-benchmarks vs their XLA formulations (real chip).

Supplementary to bench.py (the driver's single-line headline metric): prints
one JSON line PER kernel comparison.  Inputs VARY per timed iteration, so no
two timed executions are identical.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np


def bench_flash(t: int = 4096) -> dict:
    """Forward-only comparison, chain-differenced (the inherited method, to
    be re-validated — see bench.py)."""
    from tdfo_tpu.ops.pallas_kernels import flash_attention

    b, h, dh = 1, 8, 64

    def xla_attn(q, k, v):
        s = jnp.einsum("bhtd,bhsd->bhts", q, k).astype(jnp.float32) / dh**0.5
        return jnp.einsum("bhts,bhsd->bhtd", jax.nn.softmax(s, -1).astype(v.dtype), v)

    def build(attn):
        def run(kn):
            @jax.jit
            def chain(qs, ks_, vs):
                def body(c, xs):
                    q, kk, v = xs
                    o = attn(q + c.astype(q.dtype), kk, v)
                    return o.astype(jnp.float32).sum() % 1024.0, None

                c, _ = jax.lax.scan(body, jnp.float32(0), (qs, ks_, vs))
                return c

            return chain

        return run

    def make_args(kn, seed):
        xs = jax.random.split(jax.random.key(seed), 3)
        q, kk, v = (jax.random.normal(x, (kn, b, h, t, dh), jnp.bfloat16) for x in xs)
        float(jnp.sum(q.astype(jnp.float32)))
        return (q, kk, v)

    pl_sec = _chain_time(build(lambda q, k, v: flash_attention(q, k, v)),
                         make_args, ks=(8, 32))
    xla_sec = _chain_time(build(xla_attn), make_args, ks=(8, 32))
    return {
        "metric": f"flash_attention_T{t}_ms",
        "value": round(pl_sec * 1e3, 3),
        "unit": "ms",
        "xla_ms": round(xla_sec * 1e3, 3),
        "vs_baseline": round(xla_sec / max(pl_sec, 1e-9), 3),  # >1 = pallas faster
    }


def _chain_time(run, make_args, ks=(16, 96), reps=2) -> float:
    """Per-step seconds by chain-length differencing — the single shared
    implementation lives in bench.py (inherited method, to be
    re-validated by ROADMAP S0)."""
    from bench import chain_time

    return chain_time(run, make_args, ks=ks, reps=reps)


def bench_fat_adam(v: int = 2_000_000, d: int = 64, b: int = 8192) -> dict:
    """Fused fat-row Adam tier (in-place DMA kernel on TPU) vs the plain
    three-buffer gather/scatter tier on the same updates.  State is created
    inside each chain (a per-chain constant the differencing cancels) so no
    second HBM copy of a big table ever exists.
    """
    from tdfo_tpu.ops.pallas_kernels import fat_pack
    from tdfo_tpu.ops.sparse import sparse_optimizer

    opt = sparse_optimizer("adam", lr=1e-2, small_vocab_threshold=0)
    probe = jax.random.normal(jax.random.key(9), (d,))

    def build(fused: bool):
        def run(k):
            @jax.jit
            def chain(key, ids_stack, grads_stack):
                table = jax.random.uniform(key, (v, d), jnp.float32)
                if fused:
                    table = fat_pack(table, jnp.zeros((v, d), jnp.float32),
                                     jnp.zeros((v, d), jnp.float32))
                slots = opt.init(table)

                def body(carry, xs):
                    t, s = carry
                    ids, g = xs
                    t, s = opt.update(t, s, ids, g, embedding_dim=d)
                    return (t, s), None

                (t, _), _ = jax.lax.scan(body, (table, slots),
                                         (ids_stack, grads_stack))
                first = t[0, 0, :d] if fused else t[0]
                return (first @ probe).sum()

            return chain

        return run

    def make_args(k, seed):
        r = np.random.default_rng(seed)
        ids = jax.device_put(r.integers(0, v, (k, b)).astype(np.int32))
        grads = jax.device_put(r.standard_normal((k, b, d), np.float32))
        float(jnp.sum(ids) + jnp.sum(grads))
        return (jax.random.key(seed), ids, grads)

    fat_sec = _chain_time(build(fused=True), make_args)
    plain_sec = _chain_time(build(fused=False), make_args)
    return {
        "metric": f"fat_adam_V{v}_B{b}_D{d}_ms",
        "value": round(fat_sec * 1e3, 3),
        "unit": "ms",
        "plain_tier_ms": round(plain_sec * 1e3, 3),
        "vs_baseline": round(plain_sec / max(fat_sec, 1e-9), 3),  # >1 = fat faster
    }


def bench_fat_bf16(v: int = 2_000_000, d: int = 64, b: int = 8192) -> dict:
    """Quantized fat-line storage ablation: bf16 packed lines (half the
    per-line DMA bytes, in-kernel stochastic-rounding writeback keyed per
    step) vs the f32 fat tier on identical updates.  vs_baseline > 1 means
    bf16 wins — expect roughly the DMA-byte ratio at this profile, since
    the fat tier is line-traffic-bound (docs/BUDGET.md)."""
    from tdfo_tpu.ops.pallas_kernels import fat_pack
    from tdfo_tpu.ops.quant import sr_key as make_sr_key
    from tdfo_tpu.ops.sparse import sparse_optimizer

    opt = sparse_optimizer("adam", lr=1e-2, small_vocab_threshold=0)
    probe = jax.random.normal(jax.random.key(9), (d,))

    def build(dtype):
        quant = dtype != jnp.float32

        def run(k):
            @jax.jit
            def chain(key, ids_stack, grads_stack):
                table = jax.random.uniform(key, (v, d), jnp.float32)
                fat = fat_pack(table, jnp.zeros((v, d), jnp.float32),
                               jnp.zeros((v, d), jnp.float32), dtype=dtype)
                slots = opt.init(fat)

                def body(carry, xs):
                    t, s, step = carry
                    ids, g = xs
                    sk = make_sr_key(step, "bench_fat") if quant else None
                    t, s = opt.update(t, s, ids, g, embedding_dim=d,
                                      sr_key=sk)
                    return (t, s, step + 1), None

                (t, _, _), _ = jax.lax.scan(
                    body, (fat, slots, jnp.int32(0)),
                    (ids_stack, grads_stack))
                return (t[0, 0, :d].astype(jnp.float32) @ probe).sum()

            return chain

        return run

    def make_args(k, seed):
        r = np.random.default_rng(seed)
        ids = jax.device_put(r.integers(0, v, (k, b)).astype(np.int32))
        grads = jax.device_put(r.standard_normal((k, b, d), np.float32))
        float(jnp.sum(ids) + jnp.sum(grads))
        return (jax.random.key(seed), ids, grads)

    bf16_sec = _chain_time(build(jnp.bfloat16), make_args)
    f32_sec = _chain_time(build(jnp.float32), make_args)
    return {
        "metric": f"fat_adam_bf16_V{v}_B{b}_D{d}_ms",
        "value": round(bf16_sec * 1e3, 3),
        "unit": "ms",
        "f32_fat_ms": round(f32_sec * 1e3, 3),
        "vs_baseline": round(f32_sec / max(bf16_sec, 1e-9), 3),  # >1 = bf16 faster
    }


def bench_fat_int8(v: int = 2_000_000, d: int = 64, b: int = 8192) -> dict:
    """int8 byte-container fat lines (1-byte codes + the bitcast f32
    (scale, offset) sidecar + f32 adam state in ONE line: 640 B/row at
    d=64 vs 1160 B/row for plain int8 codes + sidecar + f32 slot arrays)
    vs the f32 fat tier AND the plain-int8 dedupe + scatter path on
    identical updates.  vs_baseline > 1 means the int8 fat line wins over
    f32 fat; vs_plain_int8 > 1 means it also beats the eager plain-int8
    scatter — the planner's cross-over at this profile (docs/BUDGET.md)."""
    from tdfo_tpu.ops.pallas_kernels import fat_pack
    from tdfo_tpu.ops.quant import quantize_rows, sr_key as make_sr_key
    from tdfo_tpu.ops.sparse import sparse_optimizer

    opt = sparse_optimizer("adam", lr=1e-2, small_vocab_threshold=0)
    probe = jax.random.normal(jax.random.key(9), (d,))

    def build_fat(dtype):
        quant = dtype != jnp.float32

        def run(k):
            @jax.jit
            def chain(key, ids_stack, grads_stack):
                table = jax.random.uniform(key, (v, d), jnp.float32)
                fat = fat_pack(table, jnp.zeros((v, d), jnp.float32),
                               jnp.zeros((v, d), jnp.float32), dtype=dtype)
                slots = opt.init(fat)

                def body(carry, xs):
                    t, s, step = carry
                    ids, g = xs
                    sk = make_sr_key(step, "bench_fat") if quant else None
                    t, s = opt.update(t, s, ids, g, embedding_dim=d,
                                      sr_key=sk)
                    return (t, s, step + 1), None

                (t, _, _), _ = jax.lax.scan(
                    body, (fat, slots, jnp.int32(0)),
                    (ids_stack, grads_stack))
                return (t[0, 0, :d].astype(jnp.float32) @ probe).sum()

            return chain

        return run

    def run_plain(k):
        @jax.jit
        def chain(key, ids_stack, grads_stack):
            codes, qs = quantize_rows(
                jax.random.uniform(key, (v, d), jnp.float32))
            slots = opt.init(codes)

            def body(carry, xs):
                t, s, q, step = carry
                ids, g = xs
                t, s, q = opt.update(t, s, ids, g,
                                     sr_key=make_sr_key(step, "bench_fat"),
                                     qscale=q)
                return (t, s, q, step + 1), None

            (t, _, q, _), _ = jax.lax.scan(
                body, (codes, slots, qs, jnp.int32(0)),
                (ids_stack, grads_stack))
            return ((t[0].astype(jnp.float32) * q[0, 0] + q[0, 1])
                    @ probe).sum()

        return chain

    def make_args(k, seed):
        r = np.random.default_rng(seed)
        ids = jax.device_put(r.integers(0, v, (k, b)).astype(np.int32))
        grads = jax.device_put(r.standard_normal((k, b, d), np.float32))
        float(jnp.sum(ids) + jnp.sum(grads))
        return (jax.random.key(seed), ids, grads)

    i8_sec = _chain_time(build_fat(jnp.int8), make_args)
    f32_sec = _chain_time(build_fat(jnp.float32), make_args)
    plain_sec = _chain_time(run_plain, make_args)
    return {
        "metric": f"fat_adam_int8_V{v}_B{b}_D{d}_ms",
        "value": round(i8_sec * 1e3, 3),
        "unit": "ms",
        "f32_fat_ms": round(f32_sec * 1e3, 3),
        "plain_int8_ms": round(plain_sec * 1e3, 3),
        "vs_baseline": round(f32_sec / max(i8_sec, 1e-9), 3),  # >1 = int8 faster
        "vs_plain_int8": round(plain_sec / max(i8_sec, 1e-9), 3),
    }


def bench_hot_cold_update(v: int = 10_131_227, d: int = 16, b: int = 8192,
                          k_hot: int = 16_384) -> dict:
    """Frequency-partitioned update ablation at the Criteo big-table profile
    (the largest Kaggle table: 10.13M rows, dim 16) under power-law (zipf)
    traffic: plain dedupe + XLA row-scatter over ALL ids vs the hot/cold
    split — branch-free prefix routing, scatter-free one-hot MXU update for
    the [0, 16k) head (where the lookup mass concentrates), dedupe + scatter
    for the much smaller cold residual.  Both run the SAME rowwise-adagrad
    math; vs_baseline > 1 means the split wins."""
    from tdfo_tpu.data.synthetic import zipf_ids
    from tdfo_tpu.ops.sparse import sparse_optimizer

    opt = sparse_optimizer("rowwise_adagrad", lr=1e-3)

    def build(split: bool):
        def run(k):
            @jax.jit
            def chain(ids_stack, grads_stack):
                table = jnp.zeros((v, d), jnp.float32)
                slots = opt.init(table)
                hot = jnp.zeros((k_hot, d), jnp.float32)
                hot_slots = opt.init(hot)

                def body(carry, xs):
                    t, s, h, hs = carry
                    ids, g = xs
                    if split:
                        hit = ids < k_hot
                        hp = jnp.where(hit, ids, -1)
                        ci = jnp.where(hit, -1, ids)
                        h, hs = opt.dense_update(h, hs, hp, g)
                        t, s = opt.update(t, s, ci, g)
                    else:
                        t, s = opt.update(t, s, ids, g)
                    return (t, s, h, hs), None

                (t, _, h, _), _ = jax.lax.scan(
                    body, (table, slots, hot, hot_slots),
                    (ids_stack, grads_stack))
                return t[0].sum() + h[0].sum()

            return chain

        return run

    hit_rates: list[float] = []

    def make_args(k, seed):
        r = np.random.default_rng(seed)
        ids_np = zipf_ids(r, v, (k, b))
        hit_rates.append(float((ids_np < k_hot).mean()))
        ids = jax.device_put(ids_np)
        grads = jax.device_put(r.standard_normal((k, b, d), np.float32))
        float(jnp.sum(ids) + jnp.sum(grads))
        return (ids, grads)

    split_sec = _chain_time(build(True), make_args, ks=(32, 160))
    plain_sec = _chain_time(build(False), make_args, ks=(32, 160))
    return {
        "metric": f"hot_cold_update_V{v}_B{b}_D{d}_K{k_hot}_ms",
        "value": round(split_sec * 1e3, 3),
        "unit": "ms",
        "plain_scatter_ms": round(plain_sec * 1e3, 3),
        "hit_rate": round(float(np.mean(hit_rates)), 4),
        "vs_baseline": round(plain_sec / max(split_sec, 1e-9), 3),  # >1 = split faster
    }


def bench_cache_route(v: int = 10_131_227, d: int = 16, b: int = 8192,
                      c: int = 16_384) -> dict:
    """Isolated cost of the update-cache directory route
    (``ops/sparse.py cache_route``: one ``searchsorted(method="sort")``
    into the sorted-id directory + a slot gather — branch-free) on a warm
    C=16k directory, vs the eager dedupe + XLA row-scatter update it
    displaces on non-flush steps (largest Criteo-Kaggle table,
    10.13M x 16, rowwise-adagrad, zipf a=1.2 traffic).  vs_baseline > 1 =
    the route costs less than the scatter it amortizes away; the claim the
    MANAGED_CACHING mode banks on is ~2 orders of magnitude (8k-scale
    sorts are ~tens of µs on v5e, the scatter path ~10+ ms here)."""
    from tdfo_tpu.data.synthetic import zipf_ids
    from tdfo_tpu.ops.sparse import cache_route, sparse_optimizer

    # warm directory: the hottest C ids resident — the steady state the
    # (freq, recency) retention policy converges to under power-law traffic
    dir_ids = jax.device_put(jnp.arange(c, dtype=jnp.int32))
    dir_slot = jax.device_put(jnp.arange(c, dtype=jnp.int32))

    def run_route(k):
        @jax.jit
        def chain(dir_ids, dir_slot, ids_stack):
            cache = {"ids": dir_ids, "slot": dir_slot}

            def body(carry, ids):
                # fold the carry in so no two routed batches are identical
                ids = (ids + carry) % v
                phys, hit = cache_route(cache, ids)
                return (phys.sum() + hit.sum()).astype(jnp.int32) % 128, None

            final, _ = jax.lax.scan(body, jnp.int32(0), ids_stack)
            return final

        return lambda stack: chain(dir_ids, dir_slot, stack)

    def make_route_args(k, seed):
        r = np.random.default_rng(seed)
        ids = jax.device_put(zipf_ids(r, v, (k, b)))
        float(jnp.sum(ids))
        return (ids,)

    opt = sparse_optimizer("rowwise_adagrad", lr=1e-3)

    def run_scatter(k):
        @jax.jit
        def chain(ids_stack, grads_stack):
            # table + slots created in-chain (a per-chain constant the
            # differencing cancels; see bench.py bench_big_table)
            table = jnp.zeros((v, d), jnp.float32)
            slots = opt.init(table)

            def body(carry, xs):
                t, s = carry
                ids, g = xs
                t, s = opt.update(t, s, ids, g)
                return (t, s), None

            (t, _), _ = jax.lax.scan(body, (table, slots),
                                     (ids_stack, grads_stack))
            return t[0].sum()

        return chain

    def make_scatter_args(k, seed):
        r = np.random.default_rng(seed)
        ids = jax.device_put(zipf_ids(r, v, (k, b)))
        grads = jax.device_put(r.standard_normal((k, b, d), np.float32))
        float(jnp.sum(ids) + jnp.sum(grads))
        return (ids, grads)

    # µs-scale route needs long chains to clear the per-fetch noise
    route_sec = _chain_time(run_route, make_route_args, ks=(64, 512), reps=3)
    scatter_sec = _chain_time(run_scatter, make_scatter_args, ks=(32, 160),
                              reps=3)
    return {
        "metric": f"cache_route_B{b}_C{c}_us",
        "value": round(route_sec * 1e6, 1),
        "unit": "us",
        "eager_scatter_ms": round(scatter_sec * 1e3, 3),
        "vs_baseline": round(scatter_sec / max(route_sec, 1e-9), 3),  # >1 = route cheaper
    }


def bench_flash_bwd(t: int = 4096) -> dict:
    """Training-direction comparison: flash fwd+bwd (both Pallas, O(T)
    memory) vs the [T, T]-materialising XLA attention's VJP."""
    from tdfo_tpu.ops.pallas_kernels import _xla_attention, flash_attention

    b, h, dh = 1, 8, 64

    def build(attn):
        def run(k):
            @jax.jit
            def chain(qs, ks_, vs):
                def body(c, xs):
                    q, kk, v = xs

                    def loss(q, kk, v):
                        return (attn(q + c.astype(q.dtype), kk, v) ** 2).sum().astype(jnp.float32)

                    _, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, kk, v)
                    return (sum(g.astype(jnp.float32).sum() for g in grads) % 1024.0), None

                c, _ = jax.lax.scan(body, jnp.float32(0), (qs, ks_, vs))
                return c

            return chain

        return run

    def make_args(k, seed):
        xs = jax.random.split(jax.random.key(seed), 3)
        q, kk, v = (jax.random.normal(x, (k, b, h, t, dh), jnp.bfloat16) for x in xs)
        float(jnp.sum(q.astype(jnp.float32)))
        return (q, kk, v)

    pl_sec = _chain_time(build(lambda q, k, v: flash_attention(q, k, v)),
                         make_args, ks=(4, 16))
    xla_sec = _chain_time(build(lambda q, k, v: _xla_attention(q, k, v, None)),
                          make_args, ks=(4, 16))
    return {
        "metric": f"flash_fwd_bwd_T{t}_ms",
        "value": round(pl_sec * 1e3, 3),
        "unit": "ms",
        "xla_ms": round(xla_sec * 1e3, 3),
        "vs_baseline": round(xla_sec / max(pl_sec, 1e-9), 3),  # >1 = pallas faster
    }


def bench_ring_flash(t: int = 8192) -> dict:
    """Ring attention with flash innards vs the XLA blockwise ring, fwd+bwd,
    on the real chip's 1-device mesh (seq axis 1: the ring program — shard_map
    + scan + ppermute + the Pallas custom_vjp — compiles and runs end to end;
    multi-chip rotation is exercised by the CPU-mesh tests and the driver
    dryrun)."""
    from tdfo_tpu.core.config import MeshSpec
    from tdfo_tpu.core.mesh import make_mesh
    from tdfo_tpu.parallel.ring_attention import ring_self_attention

    mesh = make_mesh(MeshSpec(data=1, model=1, seq=-1))
    b, h, dh = 1, 4, 64

    def build(impl, block_k=None):
        def run(k):
            @jax.jit
            def chain(qs, ks_, vs):
                def body(c, xs):
                    q, kk, v = xs

                    def loss(q, kk, v):
                        out = ring_self_attention(
                            mesh, q + c.astype(q.dtype), kk, v,
                            impl=impl, block_k=block_k)
                        return (out.astype(jnp.float32) ** 2).sum()

                    _, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, kk, v)
                    return (sum(g.astype(jnp.float32).sum() for g in grads) % 1024.0), None

                c, _ = jax.lax.scan(body, jnp.float32(0), (qs, ks_, vs))
                return c

            return chain

        return run

    def make_args(k, seed):
        xs = jax.random.split(jax.random.key(seed), 3)
        q, kk, v = (jax.random.normal(x, (k, b, h, t, dh), jnp.bfloat16) for x in xs)
        float(jnp.sum(q.astype(jnp.float32)))
        return (q, kk, v)

    fl_sec = _chain_time(build("flash"), make_args, ks=(2, 8))
    xla_sec = _chain_time(build("xla", block_k=512), make_args, ks=(2, 8))
    return {
        "metric": f"ring_flash_fwd_bwd_T{t}_ms",
        "value": round(fl_sec * 1e3, 3),
        "unit": "ms",
        "xla_ring_ms": round(xla_sec * 1e3, 3),
        "vs_baseline": round(xla_sec / max(fl_sec, 1e-9), 3),  # >1 = flash faster
    }


if __name__ == "__main__":
    from tdfo_tpu.core.mesh import configure_compile_cache

    configure_compile_cache()
    print(json.dumps(bench_flash()))
    print(json.dumps(bench_flash_bwd()))
    print(json.dumps(bench_fat_adam()))
    print(json.dumps(bench_fat_bf16()))
    print(json.dumps(bench_fat_int8()))
    print(json.dumps(bench_hot_cold_update()))
    print(json.dumps(bench_cache_route()))
    print(json.dumps(bench_ring_flash()))
