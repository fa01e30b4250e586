#!/usr/bin/env python3
"""Compile a configuration's serving steps at full width for a described
TPU v5e chip, with no chip attached: the paged decode step at each pool
length of the lattice and the chunked-prefill step at each prompt
bucket, as the engine jits them (Pallas kernels on).  Prints compile
seconds and ``memory_analysis()`` per step; a compile the chip's
compiler would refuse raises here.

    JAX_PLATFORMS=cpu python3 bench/tools/aot.py --config smollm-135m \
        --pool 2048 --prompt-buckets 256,512,1024,2048
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--pool", default="")
    ap.add_argument("--prompt-buckets", default="")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "bench"))
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    import repro.core.hw as hw
    from harness import spec
    from repro.configs.base import ShapeConfig
    from repro.kernels import ops
    from repro.launch.steps import make_chunk_prefill_step, make_decode_step
    from repro.models import build_model
    from repro.runtime import sharding as shd
    from repro.serve.buckets import BucketRouter, BucketSpec
    from repro.tuner import TuningCache

    conf = json.loads((ROOT / "bench" / "configs"
                       / f"{args.config}.json").read_text())
    serve = conf["serve"]
    cfg = spec.model_config(conf)
    v5e = hw.TPU_REGISTRY["tpu_v5e"]
    hw.detect = lambda num_chips=None: v5e      # the chip's parameters
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    rep = NamedSharding(mesh, PartitionSpec())
    slots, max_len, pb = serve["slots"], serve["max_len"], serve["block_size"]
    model = build_model(cfg)
    plan = shd.resolve_plan(cfg, mesh, ShapeConfig("serve", max_len, slots,
                                                   "decode"))
    router = BucketRouter(cfg, BucketSpec(max_len=max_len, min_len=32),
                          slots=slots, hw=v5e, policy="tuned",
                          cache=TuningCache(path=None), page_block=pb)

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                           sharding=rep), tree)

    params = sds(model.abstract_params())
    rows = []
    with ops.force("pallas"):
        for kv in [int(x) for x in args.pool.split(",") if x]:
            bp = router.resolve(router.bucket(kv))
            cache = dict(model.init_cache(slots, kv, abstract=True))
            cache["pos"] = jax.ShapeDtypeStruct((slots,), jnp.int32)
            step = jax.jit(make_decode_step(model, plan),
                           static_argnames=("decode_block", "page_block",
                                            "paged_decode_block"))
            t0 = time.perf_counter()
            c = step.lower(params, sds(cache),
                           jax.ShapeDtypeStruct((slots, 1), jnp.int32,
                                                sharding=rep),
                           decode_block=bp.decode_block,
                           page_tables=jax.ShapeDtypeStruct(
                               (slots, kv // pb), jnp.int32, sharding=rep),
                           page_block=pb,
                           paged_decode_block=bp.paged_decode_block).compile()
            rows.append(("decode", kv, time.perf_counter() - t0, c,
                         bp.paged_decode_block))
        for b in [int(x) for x in args.prompt_buckets.split(",") if x]:
            tiles = router.prefill_tiles(b)
            chunk = min(int(tiles[0]), b)
            cache = dict(model.init_cache(1, b, abstract=True))
            step = jax.jit(make_chunk_prefill_step(model, plan),
                           static_argnames=("prefill_tiles",))
            t0 = time.perf_counter()
            c = step.lower(params, sds(cache),
                           jax.ShapeDtypeStruct((1, chunk), jnp.int32,
                                                sharding=rep),
                           jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
                           prefill_tiles=tiles).compile()
            rows.append(("chunk", b, time.perf_counter() - t0, c, tiles))
    for kind, n, secs, c, plan_value in rows:
        m = c.memory_analysis()
        print(json.dumps({
            "step": kind, "length": n, "plan": plan_value,
            "compile_s": round(secs, 3),
            "kernel": "tpu_custom_call" in c.as_text(),
            "argument_bytes": m.argument_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes,
            "alias_bytes": m.alias_size_in_bytes}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
