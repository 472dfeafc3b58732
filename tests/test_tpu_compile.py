"""Compile rehearsal: the main path's kernels and a full-width decode step
compiled for one chip of a described TPU v5e, with no chip attached.

The TPU compiler is installed with JAX, so refusals that the Pallas
interpreter cannot show — tiles not aligned to the TPU layout, more fast
memory than a kernel may use, a program too large for the device — fail
here before any chip time is spent. Nothing runs: these tests say that
a program compiles and how many bytes it needs, never how fast it is.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test runner's
workers all import this file. The compile cache is off around the
compiles — what they would write cannot be read back without a chip.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import kernels
from repro.configs import get_arch
from repro.kernels.membench.kernel import membench_pallas
from repro.kernels.paged_attention import kernel as paged_kernel
from repro.kernels.semaphore.ops import semaphore_admission
from repro.kernels.ticket_lock.kernel import ticket_lock_pallas
from repro.kernels.xf_barrier.kernel import xf_barrier_pallas
from repro.models import build_model

# qwen3-14b's attention widths (configs/qwen3_14b.py)
KV, G, HD, PS = 8, 5, 128, 16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(sharding, tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _compile(sharding, fn, *shapes, **static):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(lambda *a: fn(*a, **static)).lower(*args).compile()


def test_semaphore_planner_at_plan_window(one_chip):
    """The sync library's Algorithm-5 planner behind
    ``scheduler.plan_admission``, at a 64-arrival window and the smoke
    run's capacity."""
    window = 64
    c = _compile(one_chip, semaphore_admission,
                 ((window,), jnp.float32), ((window,), jnp.float32),
                 capacity=8, interpret=False)
    assert "tpu_custom_call" in c.as_text()


def test_fused_paged_decode_at_qwen3_widths(one_chip):
    """KV=8, G=5, hd=128, ps=16 over a 4096-page bf16 arena. The kernel
    reads the arena in place: no temporary the size of an arena (a
    relayout of the arena would show up here)."""
    b, pages, p_cap = 8, 4096, 69
    arena = ((pages, PS, KV, HD), jnp.bfloat16)
    c = _compile(one_chip, paged_kernel.fused_paged_decode,
                 ((b, KV, G, HD), jnp.bfloat16), arena, arena,
                 ((b, p_cap), jnp.int32), ((b,), jnp.int32),
                 interpret=False)
    assert "tpu_custom_call" in c.as_text()
    arena_bytes = pages * PS * KV * HD * 2
    assert c.memory_analysis().temp_size_in_bytes < arena_bytes


@pytest.mark.parametrize("name", ["ticket_lock", "xf_barrier", "membench"])
def test_sync_kernels_compile(one_chip, name):
    n = 64
    if name == "ticket_lock":
        c = _compile(one_chip, ticket_lock_pallas, ((n,), jnp.int32),
                     ((n,), jnp.float32), ((n,), jnp.float32),
                     interpret=False)
    elif name == "xf_barrier":
        c = _compile(one_chip, xf_barrier_pallas, ((n,), jnp.int32),
                     ((), jnp.int32), ((n,), jnp.int32), ((n,), jnp.int32),
                     interpret=False)
    else:
        c = _compile(one_chip, membench_pallas, ((16, 128), jnp.float32),
                     n_steps=16, contentious=False, write=True,
                     interpret=False)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("impl", ["gather", "fused"])
def test_paged_decode_step_qwen3_full_width(one_chip, impl, monkeypatch):
    """One paged ``decode_step`` of qwen3-14b at its published widths,
    depth cut to 2 layers, shapes from ``jax.eval_shape``. The fused
    path asks the platform whether to interpret; this host is a CPU, so
    the test tells it the chip compiles.

    On one chip neither read path expands K/V to the 40 query heads: no
    op of the program has a bf16 [B, T, 40, 128] or [B, T, 8, 5, 128]
    result (T = the block table's positions)."""
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    cfg = dataclasses.replace(get_arch("qwen3-14b"), num_layers=2)
    model = build_model(cfg)
    params = _shapes(one_chip, jax.eval_shape(model.init,
                                              jax.random.PRNGKey(0)))
    b, pages, p_cap = 8, 1024, 69
    kv_leaf = jax.ShapeDtypeStruct((cfg.num_layers, pages, PS, KV, HD),
                                   jnp.bfloat16, sharding=one_chip)
    cache = {"periods": {"layer_0": {"k": kv_leaf, "v": kv_leaf}},
             "leftover": {},
             "len": jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip),
             "pages": jax.ShapeDtypeStruct((b, p_cap), jnp.int32,
                                           sharding=one_chip)}
    tok = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip)
    step = jax.jit(lambda p, c, t: model.decode_step(p, c, t,
                                                     attn_impl=impl))
    compiled = step.lower(params, cache, tok).compile()
    hlo = compiled.as_text()
    assert ("tpu_custom_call" in hlo) == (impl == "fused")
    t = p_cap * PS
    expanded = re.findall(
        rf"bf16\[{b},{t},(?:{KV * G}|{KV},{G}),{HD}\]", hlo)
    assert not expanded, expanded[:4]
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes < 16e9


@pytest.mark.parametrize("chunk", [0, 128])
def test_round_program_updates_the_arena_in_place(one_chip, chunk):
    """The engine's bucketed round program at the benchmark's shapes
    (qwen3-14b widths, 11 layers, a 1536-page arena, 16 rows, two decode
    steps, with and without a 128-token prefill chunk). The cache is
    donated, so the compiled module aliases the K and V arenas to its
    outputs; the layer scan carries them and scatters into them, so no
    op copies an arena or writes one back whole (no arena-shaped copy
    or dynamic-update-slice), and the temporaries stay below one arena
    (K and V) beside the chunk round's own [16, 128, vocab] logits."""
    from repro.serve.engine import SlotServeEngine
    layers, pages, rows = 11, 1536, 16
    cfg = dataclasses.replace(get_arch("qwen3-14b"), num_layers=layers,
                              tie_embeddings=False)
    model = build_model(cfg)
    params = _shapes(one_chip, jax.eval_shape(model.init,
                                              jax.random.PRNGKey(0)))
    # an engine whose own arrays stay tiny: only the shapes below compile
    tiny = jax.tree_util.tree_map(
        lambda s: jnp.zeros((1,) * len(s.shape), s.dtype), params)
    engine = SlotServeEngine(model, tiny, capacity=rows, max_len=784,
                             kv_layout="paged", page_size=PS, num_pages=4,
                             prefill_chunk_tokens=128, decode_chunk=2)
    view = engine.pool.cache_view()
    view["pages"] = jnp.zeros((rows, 98), jnp.int32)
    view = _shapes(one_chip, jax.tree_util.tree_map(
        lambda x: (jax.ShapeDtypeStruct((layers, pages) + x.shape[2:],
                                        x.dtype)
                   if x.ndim == 5 else x), view))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    args = (params, view, i32(rows), i32(rows),
            jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=one_chip),
            i32(rows, 128), i32(rows, 128), i32(rows, 128),
            _shapes(one_chip, jax.random.PRNGKey(0)))
    compiled = engine._bucket_round.lower(*args, steps=2,
                                          chunk=chunk).compile()
    hlo = compiled.as_text()

    arena_shape = rf"bf16\[(?:{layers},{pages},{PS}|{layers * pages * PS})" \
                  rf",{KV},{HD}\]"
    arena_params = re.findall(
        rf"= {arena_shape}\{{[^}}]*\}} parameter\((\d+)\)", hlo)
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry", hlo)
    assert aliased, "the round program aliases nothing"
    aliased = set(re.findall(r"\((\d+), \{\}", aliased.group(1)))
    entry = [p for p in arena_params
             if re.search(rf"cache__periods.*parameter\({p}\)", hlo)]
    assert len(entry) == 2 and set(entry) <= aliased, (entry, aliased)

    moved = [line.strip()[:160] for line in hlo.splitlines()
             if re.search(rf"%[\w.-]*(?:copy|dynamic-update-slice)[\w.-]* = "
                          rf"{arena_shape}", line)
             or re.search(rf"= {arena_shape}\{{[^}}]*\}} "
                          rf"(?:copy|dynamic-update-slice)\(", line)]
    assert not moved, moved[:4]

    mem = compiled.memory_analysis()
    arena_bytes = 2 * layers * pages * PS * KV * HD * 2
    assert mem.alias_size_in_bytes >= arena_bytes
    logits_bytes = rows * chunk * cfg.vocab_size * 4
    assert mem.temp_size_in_bytes < arena_bytes + logits_bytes
