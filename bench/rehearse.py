"""Size a configuration's page arena by compiling for a described v5e.

    JAX_PLATFORMS=cpu python bench/rehearse.py --config <name> \
        [--num-pages N ...] [--capacity K]

Compiles, without a chip, the largest program the window dispatches
(the engine's round at full occupancy with a prefill chunk) for one
chip of a described ``v5e:2x2``, at each arena size asked for, and
prints ``compiled.memory_analysis()``: arguments (weights and arena),
outputs, temporaries. The engine it lowers is built with a one-page
arena (nothing large is allocated here); the shapes passed in carry the
arena size being tried. A compile that passes is not a chip run: it
bounds one program from above and says nothing of time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--num-pages", type=int, nargs="+", required=True)
    ap.add_argument("--capacity", type=int, default=None)
    a = ap.parse_args(argv)

    import copy
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchlib import drive, spec

    jax.config.update("jax_enable_compilation_cache", False)
    c = spec.config(a.config)
    ref = spec.reference(c["reference"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)

    w_shapes = jax.eval_shape(lambda k: ref.draw(c, k),
                              jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(sds, ref.to_program(c, w_shapes))
    for n_pages in a.num_pages:
        small = copy.deepcopy(c)
        small["deployment"]["num_pages"] = 1
        if a.capacity:
            small["deployment"]["capacity"] = a.capacity
        # a stand-in engine: its real params never leave the host
        tiny = jax.tree_util.tree_map(
            lambda s: jnp.zeros((1,) * len(s.shape), s.dtype), params)
        engine = drive.build_engine(small, tiny, 0)
        K, C = engine.capacity, engine.prefill_chunk
        view = engine.pool.cache_view()
        # the block table's width as the real arena would have it
        width = min(-(-2 * engine.max_len // engine.pool.page_size),
                    n_pages)
        view["pages"] = jnp.zeros((K, width), jnp.int32)

        def arena(x):
            if x.ndim == 5:             # [layers, pages, page, kv, hd]
                return jax.ShapeDtypeStruct(
                    (x.shape[0], n_pages) + x.shape[2:], x.dtype,
                    sharding=one)
            return sds(x)
        view = jax.tree_util.tree_map(arena, view)
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
        args = (params, view, i32(K), i32(K),
                jax.ShapeDtypeStruct((K,), jnp.bool_, sharding=one),
                i32(K, C), i32(K, C), i32(K, C),
                sds(jax.random.PRNGKey(0)))
        compiled = engine._bucket_round.lower(
            *args, steps=engine.decode_chunk, chunk=C).compile()
        m = compiled.memory_analysis()
        gib = 2.0 ** 30
        kv = sum(x.size * x.dtype.itemsize for k, x in view.items()
                 if k not in ("len", "pages")
                 for x in jax.tree_util.tree_leaves(x))
        row = {"config": a.config, "num_pages": n_pages, "capacity": K,
               "block_table_pages": int(view["pages"].shape[1]),
               "arena_bytes": int(kv),
               "argument_bytes": int(m.argument_size_in_bytes),
               "output_bytes": int(m.output_size_in_bytes),
               "temp_bytes": int(m.temp_size_in_bytes),
               "alias_bytes": int(m.alias_size_in_bytes),
               "total_gib": (m.argument_size_in_bytes
                             + m.output_size_in_bytes
                             + m.temp_size_in_bytes
                             - m.alias_size_in_bytes) / gib}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
