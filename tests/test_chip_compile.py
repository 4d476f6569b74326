"""Compile the flush path's kernels for a DESCRIBED v5e, at real shapes.

The only file that describes the chip.  The TPU's compiler is installed
beside the CPU backend and compiles for a topology that is described,
not attached — so what Mosaic refuses on the chip (a DMA slice off the
(8, 128) tiling, a cast with no lowering) fails here, where
interpret-mode parity tests cannot see it.  Nothing runs: a compile that
passes is not a chip run (`chip_smoke.py` is).

Rules this file keeps (on-chip-measurement guide, section 2): the
topology is described inside a module-scoped fixture, never at import,
never in conftest.py, never autouse; every case compiles in this
process; the persistent compile cache is off around the compiles (such
an entry is written but cannot be read back without a chip).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from veneur_tpu.ops import compactor_eval as ce
from veneur_tpu.ops import moments_eval as me
from veneur_tpu.ops import segmented_reduce as sr
from veneur_tpu.ops import sorted_eval as se
from veneur_tpu.parallel import serving
from veneur_tpu.parallel.mesh import REPLICA_AXIS, SHARD_AXIS
from veneur_tpu.sketches import compactor as cs
from veneur_tpu.sketches import hll as hll_mod
from veneur_tpu.sketches import moments as ms

N_PCT = 3


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_tpu(monkeypatch):
    """Route code that asks `jax.default_backend()` as the chip would:
    the described device is not attached, so the process still says
    cpu."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _struct(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *structs) -> str:
    return jax.jit(fn).lower(*structs).compile().as_text()


# ---------------------------------------------------------------------------
# sorted_eval: the four entries at the shapes the repo is about
# ---------------------------------------------------------------------------

# north star (100k digests x 32 centroids), the production e2e depth 4,
# a compact-network depth bound, a DMA-path shape, the 1M-key bucket
SORTED_SHAPES = [(131072, 32), (131072, 4), (65536, 64), (16384, 256),
                 (1048576, 32)]
SORTED_ENTRIES = ["general", "uniform", "compact_bf16", "uniform_eval"]


def _sorted_case(entry: str, u: int, d: int, one_chip):
    s = lambda shape, dt=jnp.float32: _struct(one_chip, shape, dt)  # noqa
    if entry == "uniform_eval":
        return (lambda m, dep, q: se.uniform_eval(m, dep, q),
                (s((u, d)), s((u,), jnp.int32), s((N_PCT,))))
    if entry == "uniform_eval_bf16":
        return (lambda m, dep, q: se.uniform_eval(m, dep, q),
                (s((u, d), jnp.bfloat16), s((u,), jnp.int32),
                 s((N_PCT,))))
    kw = {"general": {}, "uniform": {"uniform": True},
          "uniform_bf16": {"uniform": True},
          "compact_bf16": {"compact": True}}[entry]
    vdt = jnp.bfloat16 if entry.endswith("bf16") else jnp.float32
    return (lambda m, w, a, b, q: se.weighted_eval(m, w, a, b, q, **kw),
            (s((u, d), vdt), s((u, d)), s((u,)), s((u,)), s((N_PCT,))))


@pytest.mark.parametrize("u,d", SORTED_SHAPES,
                         ids=[f"{u}x{d}" for u, d in SORTED_SHAPES])
@pytest.mark.parametrize("entry", SORTED_ENTRIES)
def test_sorted_eval_compiles_for_v5e(entry, u, d, one_chip):
    if entry == "compact_bf16" and d > se.MAX_COMPACT_DEPTH:
        # one rule, asserted instead of compiled: the dispatch gate
        # keeps deeper shapes off the packed network
        assert not se.usable_compact(u, d, "tpu")
        return
    assert se.usable(u, d, "tpu")
    fn, structs = _sorted_case(entry, u, d, one_chip)
    assert "tpu_custom_call" in _compiled_text(fn, *structs)


@pytest.mark.parametrize("entry", ["uniform_bf16", "uniform_eval_bf16"])
@pytest.mark.parametrize("u,d", [(131072, 32), (131072, 128)],
                         ids=["131072x32", "131072x128"])
def test_key_only_network_compiles_on_bf16_staging(entry, u, d,
                                                   one_chip):
    """`digest_bf16_staging` routes bf16 tiles to the key-only network;
    the v5e has no 16-bit sublane rotate, so they widen in VMEM."""
    fn, structs = _sorted_case(entry, u, d, one_chip)
    assert "tpu_custom_call" in _compiled_text(fn, *structs)


def test_usable_shapes_route_to_a_path_mosaic_accepts():
    """The rule behind the table: the DMA pipeline copies `[tile, d]`
    row slices, which Mosaic wants 128-aligned in the minor dimension,
    so every depth the dense builder can produce below 128 takes the
    classic BlockSpec path at every key bucket — and no deeper shape
    left the DMA path."""
    d = 2
    while d <= se.MAX_DEPTH:
        u = 128
        while u <= 1048576:
            assert se.usable(u, d, "tpu")
            for wide in (False, True):
                tile = se._lane_tile(u, d, wide=wide)
                nbuf = se._auto_nbuf(u, tile, d)
                assert u % (tile * nbuf) == 0
                if d < 128:
                    assert nbuf == 1, (u, d)
                elif u // tile >= se._DMA_MIN_STEPS:
                    assert nbuf > 1, (u, d)
            u *= 2
        d *= 2


# ---------------------------------------------------------------------------
# the other flush-path kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("uniform", [False, True],
                         ids=["weights", "depths"])
@pytest.mark.parametrize("u,d", [(131072, 32), (131072, 128)],
                         ids=["131072x32", "131072x128"])
def test_moments_sums_compiles_for_v5e(u, d, uniform, one_chip):
    assert me.usable(u, d, "tpu")
    k = ms.DEFAULT_K
    dw = (_struct(one_chip, (u,), jnp.int32) if uniform
          else _struct(one_chip, (u, d)))
    text = _compiled_text(
        lambda dv, w, ab, lab: me._moments_sums_pallas(
            dv, w, ab, lab, k, uniform),
        _struct(one_chip, (u, d)), dw, _struct(one_chip, (2, u)),
        _struct(one_chip, (2, u)))
    assert "tpu_custom_call" in text


def test_segment_sums_compiles_for_v5e(one_chip):
    u, c, g = 131072, 256, 4096
    assert sr.usable(u, c, "tpu")
    text = _compiled_text(
        lambda v, seg: sr._segment_sums_pallas(v, seg, g),
        _struct(one_chip, (u, c)), _struct(one_chip, (u,), jnp.int32))
    assert "tpu_custom_call" in text


def test_compactor_pass_compiles_for_v5e(one_chip):
    cap, levels, u = cs.DEFAULT_CAP, cs.DEFAULT_LEVELS, 4096
    assert ce.usable(u, cap, levels, "tpu")
    text = _compiled_text(
        lambda stage, cnt, off: ce._compact_pallas(stage, cnt, off, cap,
                                                   levels),
        _struct(one_chip, (levels * cs.STAGE_MUL * cap, u)),
        _struct(one_chip, (ce._pad8(levels), u), jnp.int32),
        _struct(one_chip, (ce._pad8(levels + cs.CLIP_ROUNDS), u),
                jnp.int32))
    assert "tpu_custom_call" in text


# ---------------------------------------------------------------------------
# the whole jitted flush the aggregator dispatches
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant", ["depth_donated", "general_donated"])
def test_serving_flush_program_compiles_for_v5e(variant, one_chip,
                                                as_tpu):
    """`serving.make_serving_flush(None)` — the mesh-less global-tier
    program, in the donated form a global launches — lowered for the
    north-star dense shape: the kernel must be IN it (a silent XLA-twin
    route would compile too)."""
    u, d = 131072, 32
    flush_fn = serving.make_serving_flush(None)
    s = lambda shape, dt=jnp.float32: _struct(one_chip, shape, dt)  # noqa
    if variant == "depth_donated":
        lowered = flush_fn.depth_variant_donated.lower(
            s((u, d)), s((u,), np.int16), s((N_PCT,)))
    else:
        lowered = flush_fn.lower_donated(
            s((u, d)), s((u, d)), s((2, u)), s((N_PCT,)), uniform=False)
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("program", ["hot_compress", "deep_tier",
                                     "shallow_tier"])
def test_hot_key_lane_programs_compile_for_v5e(program, one_chip, as_tpu):
    """What `zipf.hotset` adds to a node's programs, at the shapes its
    boot launches (`MetricAggregator.prewarm_launch`): the hot-key
    compress on its one tile (`DigestArena._hot_compress`; plain XLA —
    two sorts, two prefix sums, a [rows, width, ccap] counting reduce the
    compiler must fuse, not materialise: 80 MB if it did); the deep
    tier's program, the weighted Pallas network at DENSE_DEPTH_CAP deep
    and DEEP_TIER_MIN_ROWS rows, donated as a standalone node launches
    it; and the long tail's chunk of the depth-vector program at depth
    DEEP_TIER_THRESHOLD (32,768 touched rows in two upload chunks)."""
    from veneur_tpu.core import arena as arena_mod
    from veneur_tpu.sketches import tdigest as td

    s = lambda shape, dt=jnp.float32: _struct(one_chip, shape, dt)  # noqa
    flush_fn = serving.make_serving_flush(None)
    if program == "hot_compress":
        tile = (arena_mod.HOT_TILE_ROWS, arena_mod.HOT_TILE_WIDTH)
        compiled = serving.partial_digests.lower(
            s(tile), s(tile), compression=100.0,
            cap=td.centroid_capacity(100.0)).compile()
        assert "tpu_custom_call" not in compiled.as_text()
        mem = compiled.memory_analysis()
        # operands 2 x 0.5 MiB; the counting reduce stays fused
        assert mem.temp_size_in_bytes < 16 << 20
        return
    if program == "deep_tier":
        u, d = arena_mod.DEEP_TIER_MIN_ROWS, arena_mod.DENSE_DEPTH_CAP
        lowered = flush_fn.deep_tier_donated.lower(
            s((u, d)), s((u, d)), s((2, u)), s((N_PCT,)))
    else:
        u, d = 16384, arena_mod.DEEP_TIER_THRESHOLD
        lowered = flush_fn.depth_variant_donated.lower(
            s((u, d)), s((u,), np.int16), s((N_PCT,)))
    assert se.usable(u, d, "tpu")
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("rows", [1024, 65536],
                         ids=["node1.fanout", "sets50k"])
def test_set_estimate_compiles_for_v5e(rows, one_chip):
    """`hll.estimate` over a row bucket of p = 14 u8 registers, the
    program `MetricAggregator._dispatch_sets` launches: the 1,000 set
    keys of `node1.fanout`, and 50k rows (1 GiB of registers).  One
    fusion straight off the u8 operand — no f32 copy of the registers
    in HBM (the chip read it at 45.3 us against a Pallas form's 55.5,
    PR 27; the Pallas form left the tree with PR 30)."""
    compiled = hll_mod.estimate.lower(
        _struct(one_chip, (rows, 1 << 14), jnp.uint8)).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == rows << 14
    assert mem.temp_size_in_bytes < (rows << 14) // 8


SETS50K = (1, 65536, 1 << 14)     # global-sets50k's resident lane plane


@pytest.mark.parametrize("program", ["scatter", "scatter_small", "merge",
                                     "reset", "estimate"])
def test_resident_set_lane_programs_compile_for_v5e(program, one_chip):
    """The programs an unmeshed resident set arena launches
    (`SetArena.prewarm_lanes`), at `sets50k.union`'s plane: 1 GiB of
    p = 14 registers on one chip.  The scatter at its two padded
    lengths and the dense-row merge, in the donating form the chip runs between
    flushes (in place: the result aliases the operand); the mask reset;
    the whole-plane estimate, which reads the registers where they are
    — 4 bytes a row out, and no f32 or gathered copy of the plane among
    its temporaries.  The elementwise scatter is the one that takes a
    plane of temporaries: the compiler flattens the tiled u8 operand
    for it (a `copy` in, a `reshape` out), which is why its chunk is
    large."""
    s = lambda shape, dt: _struct(one_chip, shape, dt)  # noqa: E731
    lanes = s(SETS50K, jnp.uint8)
    plane = SETS50K[1] * SETS50K[2]
    n = (serving.LANE_SCATTER_SMALL if program == "scatter_small"
         else serving.LANE_SCATTER_CHUNK)
    r = serving.LANE_MERGE_CHUNK
    if program.startswith("scatter"):
        compiled = serving.set_lane_scatter.lower(
            lanes, s((n,), jnp.int32), s((n,), jnp.int32),
            s((n,), jnp.uint8), lane=0).compile()
    elif program == "merge":
        compiled = serving.set_lane_merge_rows.lower(
            lanes, s((r,), jnp.int32), s((r, SETS50K[2]), jnp.uint8),
            lane=0).compile()
    elif program == "reset":
        compiled = serving.set_reset_mask.lower(
            lanes, s((SETS50K[1],), jnp.uint8)).compile()
    else:
        compiled = serving.set_estimate_plane.lower(lanes).compile()
    mem = compiled.memory_analysis()
    if program in ("scatter", "scatter_small", "merge"):
        assert mem.alias_size_in_bytes == plane
    elif program == "estimate":
        assert mem.output_size_in_bytes == 4 * SETS50K[1]
    else:
        assert mem.output_size_in_bytes == plane
    if program.startswith("scatter"):
        assert mem.temp_size_in_bytes < plane + plane // 8
    else:
        assert mem.temp_size_in_bytes < plane // 8


def test_meshed_flush_program_compiles_for_v5e_2x2(topo, as_tpu):
    """The shard 2 x replica 2 program `chip_smoke.py --chips 4` runs,
    at its `[65536, 32]` dense shape: kernel present, the depth
    repartition an all-to-all inside each replica pair."""
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2),
                (SHARD_AXIS, REPLICA_AXIS))
    u, d, k2, s_rows, m = 65536, 32, 16384, 1024, 1 << 14

    def s(shape, spec, dt=jnp.float32):
        return _struct(NamedSharding(mesh, spec), shape, dt)

    lanes = P(REPLICA_AXIS, SHARD_AXIS, None)
    inputs = serving.FlushInputs(
        dense_v=s((u, d), P(SHARD_AXIS, REPLICA_AXIS)),
        dense_w=s((u, d), P(SHARD_AXIS, REPLICA_AXIS)),
        minmax=s((2, u), P(None, SHARD_AXIS)),
        hll_regs=s((2, s_rows, m), lanes, jnp.uint8),
        counter_planes=s((2, k2, 2), lanes),
        uts_regs=s((2, m), P(REPLICA_AXIS, None), jnp.uint8))
    hlo = serving.make_serving_flush(mesh).lower(
        inputs, s((N_PCT,), P(None)), uniform=True).compile().as_text()
    assert "tpu_custom_call" in hlo
    sizes = serving.collective_group_sizes(hlo, "all-to-all")
    assert sizes and set(sizes) == {2}, sizes


def test_mesh4_cell_programs_compile_for_v5e_2x2(topo, as_tpu):
    """What `benchmark`'s `mesh4.steady` launches (configuration
    `global-mesh4`: `arena_initial_capacity` 131,072, so 8,192 set rows
    and 131,072 counter rows a lane), at its own shapes: the meshed
    uniform program on `[131072, 32]`, its three collectives under the
    scopes the device trace names them by, and the set-lane kernels
    `SetArena.prewarm_lanes` runs."""
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2),
                (SHARD_AXIS, REPLICA_AXIS))
    u, d, k2, s_rows, m = 131072, 32, 131072, 8192, 1 << 14

    def s(shape, spec, dt=jnp.float32):
        return _struct(NamedSharding(mesh, spec), shape, dt)

    lanes = P(REPLICA_AXIS, SHARD_AXIS, None)
    regs = s((2, s_rows, m), lanes, jnp.uint8)
    inputs = serving.FlushInputs(
        dense_v=s((u, d), P(SHARD_AXIS, REPLICA_AXIS)),
        dense_w=s((u, d), P(SHARD_AXIS, REPLICA_AXIS)),
        minmax=s((2, u), P(None, SHARD_AXIS)),
        hll_regs=regs, counter_planes=s((2, k2, 2), lanes),
        uts_regs=s((2, m), P(REPLICA_AXIS, None), jnp.uint8))
    compiled = serving.make_serving_flush(mesh).lower(
        inputs, s((N_PCT + 1,), P(None)), uniform=True).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    for scope, opcode in (("flush.a2a", "all-to-all("),
                          ("flush.psum", "all-reduce("),
                          ("flush.pmax", "all-reduce(")):
        assert any(opcode in ln and f"/{scope}/" in ln
                   for ln in hlo.splitlines()), scope
    assert set(serving.collective_group_sizes(hlo, "all-to-all")) == {2}
    # a chip's share of the lanes is [1, 4096, 16384] u8 = 64 MiB
    assert compiled.memory_analysis().argument_size_in_bytes < 80 << 20
    one = jax.ShapeDtypeStruct((1,), jnp.int32)
    rank = jax.ShapeDtypeStruct((1,), jnp.uint8)
    serving.set_reset_rows.lower(regs, one).compile()
    serving.set_lane_scatter.lower(regs, one, one, rank, 0).compile()
    serving.set_lane_scatter_copy.lower(regs, one, one, rank, 1).compile()
