"""Compile the main-path programs for a described TPU v5e, chip absent.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
block shapes off the (8, 128) tiling, layouts it cannot relayout, or
more fast memory than a kernel may use. These tests lower and compile
the real programs for a ``v5e:2x2`` topology with the TPU compiler that
ships with JAX, so a kernel the chip would refuse fails here:

- ``intersect_count`` at the SPMD ladder widths, and at the small block
  ``delta_intersect`` picks for a tiny batch;
- ``resident_intersect`` in both layouts (vs packed rows, vs slots);
- the SPMD serve and pair programs through ``jax.shard_map`` on the
  four-chip mesh;
- the static epoch engine ``make_lcc_fn`` on one chip at R-MAT S12, its
  class-ordered ``pairwise`` program against one padded to the full
  row width, and on the four-chip mesh at R-MAT S11 with cache rows,
  its rows exchanged at their degree class.

Nothing runs, so these say nothing about results or times. The topology
is described inside a fixture (never at import), so the other tests of
a run never load the TPU library.
"""
import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

SENT = 1 << 20  # any id space: the sentinel only bounds valid ids


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # the TPU compiler logs to the temporary directory unless told not to
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices), ("rank",))


def _i32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("e,wa,wb,block_e", [
    (1024, 16, 16, 128),
    (1024, 64, 64, 128),
    (1024, 256, 256, 128),
    (1024, 256, 4096, 128),  # a hub row against the full buffer width
    (8, 3, 37, 8),  # tiny streaming batch: one block spans it
])
def test_intersect_count_compiles(one_chip, e, wa, wb, block_e):
    from repro.kernels.intersect_count import intersect_count

    fn = jax.jit(functools.partial(
        intersect_count, sentinel=SENT, block_e=block_e
    ))
    compiled = fn.lower(
        _i32((e, wa), one_chip), _i32((e, wb), one_chip)
    ).compile()
    assert _has_kernel(compiled)
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("layout", ["rows", "slots"])
def test_resident_intersect_compiles(one_chip, layout):
    from repro.kernels.resident_intersect import resident_intersect

    s, w, e = 512, 256, 1024
    res = _i32((s, w), one_chip)
    sa = _i32((e,), one_chip)
    if layout == "rows":
        fn = functools.partial(resident_intersect, sentinel=SENT)
        lowered = jax.jit(fn).lower(res, sa, _i32((e, 64), one_chip))
    else:
        fn = functools.partial(
            lambda r, a, b, **kw: resident_intersect(r, a, slots_b=b, **kw),
            sentinel=SENT,
        )
        lowered = jax.jit(fn).lower(res, sa, _i32((e,), one_chip))
    assert _has_kernel(lowered.compile())


def test_spmd_programs_compile_on_2x2_mesh(mesh4):
    from repro.core.partition import partition_1d
    from repro.distributed.spmd_runtime import SpmdIntersectExecutor

    p, n = 4, SENT
    ex = SpmdIntersectExecutor(
        partition_1d(n, p), n, p=p, mesh=mesh4, use_kernel=True,
        interpret=False,
    )
    h, w = 64, 256
    serve_cfg = ((8, 16), (8, 64), (8, 256))
    f_pad = 128  # pow-2 capacity >= p * sum(s_b)
    pair_cfg = ((128, 16, 128), (256, 64, 128), (8, 256, 8))
    e_tot = sum(e for e, _, _ in pair_cfg)
    sh = NamedSharding(mesh4, P("rank"))

    serve = ex._fn_serve(h, w, serve_cfg, f_pad).lower(
        _i32((p, h, w), sh), _i32((p, p, sum(s for s, _ in serve_cfg)), sh)
    ).compile()
    assert "all-to-all" in serve.as_text()

    pairs = ex._fn_pairs(h, f_pad, w, pair_cfg).lower(
        _i32((p, h, w), sh),
        _i32((p, f_pad, w), sh),
        _i32((p, e_tot), sh),
        _i32((p, e_tot), sh),
        jax.ShapeDtypeStruct((p, e_tot), jnp.bool_, sharding=sh),
    ).compile()
    assert _has_kernel(pairs)


def _engine_args(prob, mesh):
    """Shapes of the epoch program's arguments (``device_args``)."""
    from repro.core.rma import class_layout

    layout = class_layout(prob)
    sharded = NamedSharding(mesh, P("dev"))
    return [
        jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharded)
        for x in (prob.rows_ext, prob.degrees, layout.slot_u, layout.slot_v,
                  layout.fetch_idx)
    ] + [jax.ShapeDtypeStruct(prob.cache_rows.shape, prob.cache_rows.dtype,
                              sharding=NamedSharding(mesh, P()))]


def test_static_engine_compiles_on_one_chip(topo):
    from repro.core.async_engine import make_lcc_fn
    from repro.core.rma import build_sharded_problem
    from repro.graphs.rmat import rmat_graph

    prob = build_sharded_problem(rmat_graph(12, 16, seed=0), 1, n_rounds=4)
    mesh = Mesh(np.array(topo.devices[:1]), ("dev",))
    compiled = make_lcc_fn(prob, mesh, method="hybrid").lower(
        *_engine_args(prob, mesh)).compile()
    mem = compiled.memory_analysis()
    # the whole epoch must fit one v5e chip's 16 GB of HBM
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9


def test_class_ordered_pairwise_needs_less_than_padded(topo, monkeypatch):
    """The ``pairwise`` epoch program gathers and compares each edge slot
    at its degree class; with every slot at the full row width (a ladder
    of one class, the program before degree classes) the same graph's
    program needs more temporary memory."""
    from repro.core import rma
    from repro.core.async_engine import make_lcc_fn
    from repro.graphs.rmat import rmat_graph

    csr = rmat_graph(12, 16, seed=0)
    mesh = Mesh(np.array(topo.devices[:1]), ("dev",))

    def temps(prob):
        compiled = make_lcc_fn(prob, mesh, method="pairwise").lower(
            *_engine_args(prob, mesh)).compile()
        return compiled.memory_analysis().temp_size_in_bytes

    classed = rma.build_sharded_problem(csr, 1, n_rounds=4)
    assert len(rma.class_layout(classed).caps) > 1
    monkeypatch.setattr(rma, "class_widths", lambda width: np.array([width]))
    padded = rma.build_sharded_problem(csr, 1, n_rounds=4)
    w = padded.width
    assert rma.class_layout(padded).widths == ((w, w),)
    assert temps(classed) < temps(padded)


def test_class_width_exchange_compiles_on_2x2(topo):
    """The p=4 epoch program with cache rows compiles for the 2x2 mesh
    with one exchange a round (the first before the round loop, the
    next issued inside it ahead of the compare), and no operand of them
    is as wide as the rows: the rows cross at their degree class."""
    import re

    from repro.core.async_engine import make_lcc_fn
    from repro.core.cache import build_static_degree_cache
    from repro.core.rma import build_sharded_problem, class_layout
    from repro.graphs.rmat import rmat_graph

    csr = rmat_graph(11, 16, seed=0)
    prob = build_sharded_problem(
        csr, 4, n_rounds=2, cache=build_static_degree_cache(csr.degrees, 8))
    assert len(class_layout(prob).fetch) == 3
    mesh = Mesh(np.array(topo.devices), ("dev",))
    text = make_lcc_fn(prob, mesh, method="pairwise").lower(
        *_engine_args(prob, mesh)).compile().as_text()
    a2a = [line for line in text.splitlines() if " all-to-all(" in line]
    assert len(a2a) == prob.n_rounds == 2
    dims = {int(d) for line in a2a
            for shape in re.findall(r"s32\[([0-9,]+)\]", line)
            for d in shape.split(",")}
    assert dims and prob.width not in dims
