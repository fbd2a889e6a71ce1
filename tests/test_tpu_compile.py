"""Compile the GPIC kernels and the whole ``gpic`` program for a TPU v5e.

No chip is needed: the TPU compiler is installed, and it compiles for a
chip that is described (``v5e:2x2``) but not attached. What interpret mode
cannot see shows up here — tiles Mosaic refuses, VMEM overruns, a program
that does not fit HBM. Shapes are the paper's Experiment II (n = 45,000,
m = 2) with the tiles ``kernels/tuning.py`` picks there; the sharded
programs run on a mesh of the four described chips (11,250 rows each).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and a test worker that imported this
file without running it must not take it.
"""
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.affinity import block_plan
from repro.kernels import ops
from repro.kernels.affinity import affinity_and_degree
from repro.kernels.block_sparse import block_sparse_matmat
from repro.kernels.gram import gram
from repro.kernels.kmeans_assign import kmeans_assign
from repro.kernels.power_step import degree_normalized_matmat
from repro.kernels.row_topk import row_topk
from repro.kernels.streaming import affinity_degree_streaming, affinity_matmat
from repro.kernels.tuning import choose_tiles

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)     # the benchmark's ``chipbench`` package

N, M, R, K = 45_000, 2, 2, 4
TILE, _ = choose_tiles(N, r=R, m=M)
NP = -(-N // TILE) * TILE                 # padded storage edge of A

#: compiled temp memory of gpic(engine='explicit') at n = 45,000: one
#: stored A is 8.12 GB in f32 (45,056² · 4 B) and 4.06 GB in bf16; two
#: copies were 16.30 and 8.16 GB
EXPLICIT_TEMP_BOUND = {jnp.float32: 9.0e9, jnp.bfloat16: 4.6e9}
#: the streaming engine stores no A: its temporaries are O(n) vectors and
#: tiles, far below the 4.06 GB of even a bf16 (n, n) array
STREAMING_TEMP_BOUND = 0.5e9
#: per-chip temp of the sharded explicit engine on 4 chips: one f32 row
#: stripe of A, 11,264 × 45,056 · 4 B = 2.03 GB (two copies would be 4.06)
CHIPS = 4
STRIPE = -(-(N // CHIPS) // TILE) * TILE
SHARDED_TEMP_BOUND = {"explicit": 2.3e9, "streaming": STREAMING_TEMP_BOUND}


@pytest.fixture(scope="module")
def v5e_devices():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no TPU
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without the chip: keep the cache out
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_devices):
    return SingleDeviceSharding(v5e_devices[0])


def _spec(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _assert_compiles_to_kernel(lowered) -> None:
    assert "tpu_custom_call" in lowered.compile().as_text()


KERNELS = {
    "affinity_and_degree": lambda s: affinity_and_degree.lower(
        s((N, M)), kind="rbf", sigma=0.3, tm=TILE, tn=TILE),
    "degree_normalized_matmat": lambda s: degree_normalized_matmat.lower(
        s((NP, NP)), s((N, R)), s((N,)), tm=TILE, tn=TILE),
    "affinity_matmat": lambda s: affinity_matmat.lower(
        s((N, M)), s((N, R)), s((N,)), kind="rbf", sigma=0.3,
        tm=TILE, tn=TILE),
    "affinity_degree_streaming": lambda s: affinity_degree_streaming.lower(
        s((N, M)), kind="rbf", sigma=0.3, tm=TILE, tn=TILE),
    "block_sparse_matmat": lambda s: block_sparse_matmat.lower(
        s((NP, NP)), s((N, R)), s((N,)),
        s((NP // TILE,), jnp.int32), s((NP // TILE, NP // TILE), jnp.int32),
        s((), jnp.int32), tm=TILE, tn=TILE),
    "row_topk": lambda s: row_topk.lower(
        s((N, M)), k=10, stat="similarity", kind="rbf", sigma=0.3,
        tm=TILE, tn=TILE),
    "gram": lambda s: gram.lower(s((N, 2 * R))),
    "kmeans_assign": lambda s: kmeans_assign.lower(s((N, R)), s((K, R))),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    _assert_compiles_to_kernel(KERNELS[name](
        lambda shape, dtype=jnp.float32: _spec(one_chip, shape, dtype)))


def test_block_plan_feeds_scalar_prefetch(one_chip):
    """The plan as the explicit engine builds it — traced ``max_b`` as a
    grid dimension — in one program with the block-sparse sweep."""
    def sweep(a, live, v, d):
        counts, col_idx, max_b = block_plan(live)
        return block_sparse_matmat(a, v, d, counts, col_idx, max_b,
                                   tm=TILE, tn=TILE)
    _assert_compiles_to_kernel(jax.jit(sweep).lower(
        _spec(one_chip, (NP, NP)),
        _spec(one_chip, (NP // TILE, NP // TILE), jnp.bool_),
        _spec(one_chip, (N, R)), _spec(one_chip, (N,))))


def _compiled_gpic_temp(monkeypatch, sharding, **kw) -> int:
    """Compile the whole jitted ``gpic`` (rbf, 400 sweeps) for the
    described chip; returns its temp memory in bytes."""
    from repro.core.gpic import gpic
    # the kernels pick interpret mode from the backend JAX runs on; this
    # compile targets the described chip, so the test steers it
    monkeypatch.setattr(ops, "_INTERPRET", False)
    key = jax.eval_shape(lambda: jax.random.key(0))
    compiled = gpic.lower(
        _spec(sharding, (N, M)), K,
        key=_spec(sharding, key.shape, key.dtype),
        affinity_kind="rbf", sigma=0.3, max_iter=400, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled.memory_analysis().temp_size_in_bytes


@pytest.mark.parametrize("a_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_whole_gpic_stores_one_copy_of_a(one_chip, monkeypatch, a_dtype):
    """The explicit engine compiles for one v5e, and its temp memory is one
    stored A, not two."""
    temp = _compiled_gpic_temp(monkeypatch, one_chip, engine="explicit",
                               a_dtype=a_dtype)
    lower = NP * NP * jnp.dtype(a_dtype).itemsize
    assert lower <= temp <= EXPLICIT_TEMP_BOUND[a_dtype], temp


def test_whole_gpic_streaming_stores_no_a(one_chip, monkeypatch):
    temp = _compiled_gpic_temp(monkeypatch, one_chip, engine="streaming")
    assert temp <= STREAMING_TEMP_BOUND, temp


@pytest.mark.parametrize("engine", ["explicit", "streaming"])
def test_sharded_gpic_compiles_for_four_chips(v5e_devices, monkeypatch,
                                              engine):
    """``distributed_gpic`` on a mesh of the four described chips: the
    ring (streaming) or one A stripe per chip (explicit), each chip's
    temp memory its own share."""
    from repro.core.distributed import distributed_gpic
    monkeypatch.setattr(ops, "_INTERPRET", False)
    mesh = Mesh(np.asarray(v5e_devices[:CHIPS]), ("data",))
    key = jax.eval_shape(lambda: jax.random.key(0))
    compiled = distributed_gpic.lower(
        jax.ShapeDtypeStruct((N, M), jnp.float32,
                             sharding=NamedSharding(mesh, P("data"))),
        K, key=jax.ShapeDtypeStruct(key.shape, key.dtype,
                                    sharding=NamedSharding(mesh, P())),
        mesh=mesh, affinity_kind="rbf", sigma=0.3, max_iter=400,
        engine=engine).compile()
    assert "tpu_custom_call" in compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    lower = STRIPE * NP * 4 if engine == "explicit" else 0
    assert lower <= temp <= SHARDED_TEMP_BOUND[engine], temp


def test_tile_is_the_autotuners_choice():
    """The shapes above are the ones the engines run at n = 45,000."""
    assert (TILE, NP) == (512, 45_056)
    assert ops.resolve_tiles(N, r=R, m=M) == (TILE, TILE)


#: n of the subsample cell: the op names do not depend on n, and the
#: programs compile in seconds there
N_NAMES = 4_500


def _reader_op_names(engine: str) -> set:
    """The op names the benchmark's kernel readers
    (``chipbench/metrics/*.kernel_ms.py``) key on for ``engine``."""
    names = set()
    for metric, attr in (("sweep.kernel_ms", "SWEEP_KERNEL"),
                         ("build.kernel_ms", "BUILD_KERNEL"),
                         ("kmeans.kernel_ms", "KMEANS_KERNEL")):
        spec = importlib.util.spec_from_file_location(
            "reader_" + metric.replace(".", "_"),
            os.path.join(ROOT, "chipbench", "metrics", metric + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        keyed = getattr(mod, attr)
        names.add(keyed[engine] if isinstance(keyed, dict) else keyed)
    return names


def _hlo_ops(text: str) -> set:
    """The op of every instruction in compiled HLO text, reduced as the
    benchmark reduces a device event's name (``chipbench.trace.Event``)."""
    from chipbench.trace import Event
    return {Event(line.strip().removeprefix("ROOT "), 0.0, 0.0).op
            for line in text.splitlines() if " = " in line}


@pytest.mark.parametrize("program", ["explicit", "streaming",
                                     "sharded-explicit"])
def test_reader_op_names_are_in_the_compiled_program(
        v5e_devices, one_chip, monkeypatch, program):
    """Every kernel a benchmark reader times keeps its op name in the
    program the cells run: a rename fails here, not silently on the
    chip."""
    from repro.core.distributed import distributed_gpic
    from repro.core.gpic import gpic
    monkeypatch.setattr(ops, "_INTERPRET", False)
    key = jax.eval_shape(lambda: jax.random.key(0))
    engine = program.rpartition("-")[2]
    kw = dict(affinity_kind="rbf", sigma=0.3, max_iter=400, engine=engine)
    if program.startswith("sharded"):
        mesh = Mesh(np.asarray(v5e_devices[:CHIPS]), ("data",))
        lowered = distributed_gpic.lower(
            jax.ShapeDtypeStruct((N_NAMES, M), jnp.float32,
                                 sharding=NamedSharding(mesh, P("data"))),
            K, key=jax.ShapeDtypeStruct(key.shape, key.dtype,
                                        sharding=NamedSharding(mesh, P())),
            mesh=mesh, **kw)
    else:
        lowered = gpic.lower(
            _spec(one_chip, (N_NAMES, M)), K,
            key=_spec(one_chip, key.shape, key.dtype), **kw)
    found = _hlo_ops(lowered.compile().as_text())
    assert _reader_op_names(engine) <= found, sorted(found)
