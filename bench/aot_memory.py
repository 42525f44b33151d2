#!/usr/bin/env python3
"""Compile a configuration's largest serving programs for a described (not
attached) TPU v5e and print ``memory_analysis()`` of each: the paged decode
block at the largest context bucket and block length, sampled and greedy,
and the slot prefill at the largest bucket.  Needs no chip.

    JAX_PLATFORMS=cpu python3 bench/aot_memory.py qwen3-14b [max_batch \
        [num_pages]]
"""
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from harness import cell, weights
    from repro.models import NOSHARD, init_cache
    from repro.serving import engine as E

    jax.config.update("jax_enable_compilation_cache", False)
    conf = cell.load_json(BENCH, "configs", argv[0] + ".json")
    ecfg = dict(conf["engine"])
    if len(argv) > 2:
        ecfg["num_pages"] = int(argv[2])
    if len(argv) > 1:
        ecfg["max_batch"] = int(argv[1])
    cfg = cell.program_config(conf, ["num_hidden_layers"])
    spec = weights.spec_of(conf)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one)

    params = weights._nest({p: sds(s, d) for p, (s, d, _) in
                            weights.leaf_shapes(spec).items()})
    B, L, ps = ecfg["max_batch"], ecfg["max_len"], 16
    caches = jax.tree.map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda: init_cache(cfg, B, L, dtype=jnp.bfloat16,
                                          paged_pool=(ecfg["num_pages"], ps))))
    row = lambda dt: sds((B,), dt)
    lanes = (sds((B, 2), "uint32"), row("float32"), row("int32"),
             row("float32"))
    pool = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(caches))
    print(f"{conf['arch']} layers={cfg.num_layers} max_batch={B} "
          f"max_len={L}: weights {weights.param_bytes(spec)} B, "
          f"pool {pool} B")
    for sampled in (True, False):
        c = E._paged_decode_block_kernel.lower(
            cfg, NOSHARD, 64, sampled, params, row("int32"), caches,
            sds((B, L // ps), "int32"), row("int32"), row("bool"),
            *lanes).compile()
        print(f"decode ctx={L} k=64 sampled={sampled}: "
              f"{c.memory_analysis()}", flush=True)
    b = L // 2
    c = E._prefill_kernel.lower(
        cfg, NOSHARD, True, params, sds((1, b), "int32"), sds((), "int32"),
        caches, sds((), "int32"), sds((1, b // ps), "int32"), row("int32"),
        row("int32"), *lanes).compile()
    print(f"prefill bucket={b} sampled=True: {c.memory_analysis()}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
