"""Compile the programs that touch the paged block pool at the colocated
qwen2.5-3b cell's shapes and write their optimized HLO.

    python scripts/paged_pool_hlo.py [--root DIR] [--tag TAG] --out OUTDIR

Compiles, from shapes alone, the decode step on the paged kernel, a
chunked prefill of a 256-token prompt in chunks of 128 and
``scatter_blocks`` of a 128-block payload, each with the pool donated
(32 slots, capacity 2560, block size 8: the pool of
``qwen2.5-3b.coloc.decode-heavy``), from ``DIR/src`` (default: this
checkout's). Writes ``OUTDIR/<TAG>.<program>.hlo.txt`` and prints, per
program, its temporaries and every operation whose result has the pool's
block count as its leading dimension: a copy or transpose of the pool
would be one. On a machine without a TPU it compiles for a described
v5e.
"""
from __future__ import annotations

import argparse
import os
import re
import sys
from pathlib import Path


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--root", default=".")
    ap.add_argument("--tag", default="change")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    sys.path.insert(0, str(Path(args.root).resolve() / "src"))
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from repro.configs import get_config
    from repro.models import transformer as T

    jax.config.update("jax_enable_compilation_cache", False)
    dev = jax.devices()[0]
    where = dev.device_kind
    if dev.platform != "tpu":
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        dev = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0]
        where = f"a described {dev.device_kind}, no chip"
    one = SingleDeviceSharding(dev)

    def shape(s, dtype=None):
        return jax.ShapeDtypeStruct(s if dtype else s.shape,
                                    dtype or s.dtype, sharding=one)

    cfg = get_config("qwen2.5-3b")
    L, slots, nb, bs = cfg.num_layers, 32, 2560 // 8, 8
    N = 1 + L * nb * (slots + 4)
    params = jax.tree.map(shape, T.abstract_params(cfg))
    pool = jax.tree.map(shape, jax.eval_shape(
        lambda: T.init_block_pool(cfg, N, bs)))
    i32 = jnp.int32
    ids = shape((L, 128), i32)
    payload = jax.tree.map(shape, jax.eval_shape(T.gather_blocks, pool, ids))
    programs = {
        "decode_step_paged": jax.jit(
            lambda p, pool, tbl, pos, t: T.decode_step_paged(
                p, cfg, pool, tbl, pos, t, impl="pallas"),
            donate_argnums=(1,)).lower(
                params, pool, shape((L, slots, nb), i32),
                shape((slots,), i32), shape((slots,), i32)),
        "prefill_chunked_paged": jax.jit(
            lambda p, t, pool, tbl: T.prefill_chunked_paged(
                p, cfg, {"tokens": t}, 128, pool, tbl),
            donate_argnums=(2,)).lower(
                params, shape((1, 256), i32), pool, shape((L, 32), i32)),
        "scatter_blocks": jax.jit(T.scatter_blocks, donate_argnums=(0,))
        .lower(pool, ids, payload),
    }
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    pool_op = re.compile(r"= \S*\[%d,[^ ]* ([\w-]+)\(" % N)
    for name, lowered in programs.items():
        compiled = lowered.compile()
        text = compiled.as_text()
        (out / f"{args.tag}.{name}.hlo.txt").write_text(text)
        ops = [line.strip()[:150] for line in text.splitlines()
               if (m := pool_op.search(line))
               and m.group(1) not in ("while", "tuple", "parameter")]
        print(f"== {args.tag} {name} for {where}: temp "
              f"{compiled.memory_analysis().temp_size_in_bytes} B; "
              f"pool-shaped ops {len(ops)}")
        for line in ops:
            print("   ", line)


if __name__ == "__main__":
    main()
