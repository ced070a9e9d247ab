"""The segment kernels compile for a TPU v5e that is described, not
attached: Mosaic's verdict on each kernel as it stands, at the real
shapes of the Higgs cell (10.5M x 128 lanes), of the Epsilon cell
(409,600 x 2,048 lanes, 2,000 columns x 64 bins) and of the Bosch cell
(1,015,808 x 1,024 lanes, 968 columns x 64 bins), with no chip.  `test_pallas_segment.py` runs
the same kernels in interpret mode, which says nothing about what Mosaic
accepts (an unaligned slice, a layout it cannot apply, too much VMEM).
Nothing runs here, so nothing is said about results or times.

The topology is described inside a fixture, never at import: one process
at a time may load the TPU's library, and every xdist worker imports
every test file.  These tests stay in this one file for the same reason.
"""
import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops import pallas_segment as pseg
from lightgbm_tpu.ops import segment as seg
from lightgbm_tpu.ops import state_columns as scols

ROWS, LANES, FEATURES, BINS = 10_502_408, 128, 28, 256


@pytest.fixture(scope="module")
def topo():
    import os
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


#: the Epsilon cell: 400,000 rows padded to whole chunks
WIDE_ROWS, WIDE_LANES, WIDE_FEATURES, WIDE_BINS = 409_600, 2048, 2000, 64


def _partition_args(sharding, rows=ROWS, lanes=LANES, features=FEATURES,
                    bins=BINS):
    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    i32, f32, flag = (shape((), jnp.int32), shape((), jnp.float32),
                      shape((), jnp.bool_))
    payload = shape((rows + seg.GUARD, lanes), jnp.float32)
    pred = seg.SplitPredicate(
        col=i32, threshold=i32, default_left=flag, is_cat=flag,
        missing_type=i32, num_bin=i32, default_bin=i32, offset=i32,
        identity=flag, bitset=shape((bins,), jnp.int32))
    # `right_first` is data to the kernels, as the grower passes it
    return (payload, payload, i32, i32, pred, f32, f32, features + 3, bins,
            flag)


@pytest.mark.parametrize("lanes,bins", [(LANES, BINS), (LANES, 255),
                                        (256, 255), (384, 64),
                                        (LANES, 1024), (512, 255)])
def test_partition_acc_compiles_for_v5e(one_chip, lanes, bins):
    """The accumulator partition at the Higgs cell's shape, at its 255
    bins (eight words of packed bitset, the last short of a bit) and past
    128 lanes, where the split column's window is a lane slice of the ring
    at a traced offset (two chunks a trip at 256 lanes, one at 384 and at
    512, the widest one pass takes; as many rows as fill the chip the
    same), and at 1,024 bins, where the column is read out at HIGHEST.
    Pass A's index arithmetic has rows in lanes: the NT product that
    reads the column out, the vector shifts of the categorical test and
    the sublane broadcast into the one-hot are Mosaic's to accept; and so
    is its placement: a [C + 24, C] one-hot's product stored to a scratch,
    and loads and stores of [C + 8, P] windows of the scratch and of the
    accumulators at traced starts that are multiples of 8.  Since PR 38
    each accumulator is a ring of three windows and a chunk's tail that
    a flush's DMA reads at a traced window, a put that crosses the ring's
    end stores a second window of the scratch at the ring's head, and
    pass B places the head of its rotated chunk the same way; the scratch
    list holds no flush stage and no blend buffer.  Mosaic's verdict on
    the VMEM asked for is the check of `_acc_plan_bytes`, at the widest
    shapes its gates admit (512 lanes here, a 512-lane block beside its
    split-window ring below)."""
    assert pseg.partition_acc_fits_vmem(lanes, bins)
    lowered = pseg._partition_segment_acc.lower(
        *_partition_args(one_chip, ROWS * LANES // lanes, lanes, bins=bins))
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_partition_blocks_compiles_for_v5e_at_epsilon(one_chip):
    """Four 512-lane passes of the accumulator kernel and the split-window
    snapshot, as `partition_engine` picks them at 2,048 lanes."""
    assert pseg.partition_blocks_fits_vmem(WIDE_LANES, WIDE_BINS)
    lowered = pseg._partition_segment_acc_blocks.lower(
        *_partition_args(one_chip, WIDE_ROWS, WIDE_LANES, WIDE_FEATURES,
                         WIDE_BINS))
    assert lowered.compile().as_text().count("tpu_custom_call") >= 5


#: the Bosch cell: 1,000,000 rows padded to whole 16,384-row blocks, 968
#: columns and 10 value columns in 1,024 lanes
BOSCH_ROWS, BOSCH_LANES, BOSCH_FEATURES, BOSCH_BINS = 1_015_808, 1024, 968, 64


@pytest.mark.parametrize("engine,calls", [("_partition_segment_acc_blocks", 3),
                                          ("_partition_segment", 1)])
def test_band_partitions_compile_for_v5e_at_bosch(one_chip, engine, calls):
    """Both engines of the 640-1,664-lane band at the Bosch cell's shape:
    two 512-lane passes of the accumulator kernel and the split-window
    snapshot, which `partition_engine` picks there, and the
    read-modify-write kernel's one pass over 1,024 lanes, which the race
    of `exp/race_partition_band.py` runs beside it."""
    assert pseg.partition_blocks_fits_vmem(BOSCH_LANES, BOSCH_BINS)
    assert pseg.partition_fits_vmem(BOSCH_LANES, BOSCH_BINS)
    assert not pseg.partition_acc_fits_vmem(BOSCH_LANES, BOSCH_BINS)
    lowered = getattr(pseg, engine).lower(
        *_partition_args(one_chip, BOSCH_ROWS, BOSCH_LANES, BOSCH_FEATURES,
                         BOSCH_BINS))
    assert lowered.compile().as_text().count("tpu_custom_call") >= calls


def test_histogram_compiles_for_v5e_at_bosch(one_chip):
    """The histogram at 968 columns x 64 bins (eight column tiles, the
    last of 72 columns) over full 1,024-lane rows."""
    assert pseg.fits_vmem(BOSCH_FEATURES, BOSCH_BINS, BOSCH_LANES)
    payload, _, i32 = _partition_args(
        one_chip, BOSCH_ROWS, BOSCH_LANES, BOSCH_FEATURES, BOSCH_BINS)[:3]
    lowered = pseg._segment_histogram.lower(
        payload, i32, i32, num_features=BOSCH_FEATURES, num_bins=BOSCH_BINS,
        grad_col=BOSCH_FEATURES, hess_col=BOSCH_FEATURES + 1,
        cnt_col=BOSCH_FEATURES + 2, interpret=False)
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_histogram_compiles_for_v5e_at_epsilon(one_chip):
    """The single-pass histogram at 2,000 columns x 64 bins (16 column
    tiles of 32 groups of four) over full 2,048-lane rows."""
    assert pseg.fits_vmem(WIDE_FEATURES, WIDE_BINS, WIDE_LANES)
    payload, _, i32 = _partition_args(
        one_chip, WIDE_ROWS, WIDE_LANES, WIDE_FEATURES, WIDE_BINS)[:3]
    lowered = pseg._segment_histogram.lower(
        payload, i32, i32, num_features=WIDE_FEATURES, num_bins=WIDE_BINS,
        grad_col=WIDE_FEATURES, hess_col=WIDE_FEATURES + 1,
        cnt_col=WIDE_FEATURES + 2, interpret=False)
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("features,bins", [(FEATURES, 255), (67, 256),
                                           (30, 100), (19, 37)])
def test_histogram_compiles_for_v5e_narrow(one_chip, features, bins):
    """The histogram at the Higgs and Criteo cells' shapes (128 lanes, two
    features a group) and at bin counts whose top high block is ragged."""
    assert pseg.fits_vmem(features, bins, LANES)
    payload, _, i32 = _partition_args(one_chip)[:3]
    lowered = pseg._segment_histogram.lower(
        payload, i32, i32, num_features=features, num_bins=bins,
        grad_col=features, hess_col=features + 1, cnt_col=features + 2,
        interpret=False)
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("rows,lanes,first", [
    (ROWS + seg.GUARD, LANES, FEATURES),
    (WIDE_ROWS + seg.GUARD, WIDE_LANES, WIDE_FEATURES),
    (2_277_376 + seg.GUARD, 256, 125)])
def test_state_column_kernels_compile_for_v5e(one_chip, rows, lanes, first):
    """The three state-column kernels at the Higgs and Epsilon cells'
    shapes (one lane tile of the payload's, the last block of rows
    ragged, two- and three-row blocks of the compact vectors) and, at the
    MS LTR cell's rows, with the columns across a tile edge: the
    transposes, the single-sublane stores and the lane broadcast are
    Mosaic's to accept."""
    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    label, weight, cnt, idx, score, grad, hess, value = range(first,
                                                              first + 8)
    payload = shape((rows, lanes))
    assert scols._tiles((label, value))[1] == (2 if first == 125 else 1)
    for lowered in (
            scols._state_cols_read.lower(
                payload, cols=(score, label, weight, cnt, idx)),
            scols._state_cols_write.lower(payload, shape((2, rows)),
                                          cols=(grad, hess)),
            scols._state_cols_write.lower(payload, shape((3, rows)),
                                          cols=(grad, hess, cnt)),
            scols._state_cols_axpy.lower(
                payload, shape((), jnp.int32), shape(()),
                shape((), jnp.bool_), src=value, dst_range=(score, score))):
        assert "tpu_custom_call" in lowered.compile().as_text()


def test_state_column_kernels_compile_on_four_v5e(topo):
    """As `_FastState` runs them on a mesh (`criteo-dp4-train`: 10M rows
    and a GUARD tail a chip, the wide index layout's columns): each
    device's block of rows takes the kernel on its own under `shard_map`,
    and nothing is gathered to do it."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS
    mesh = Mesh(np.array(topo.devices), ("data",))
    by_rows, by_lanes = PS("data", None), PS(None, "data")
    rows = 4 * (10_000_000 + seg.GUARD)

    def shape(dims, spec, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=NamedSharding(mesh, spec))

    def on_blocks(fn, in_specs, out_specs):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                     out_specs=out_specs, check_vma=False),
                       donate_argnums=(0,))

    payload = shape((rows, LANES), by_rows)
    for lowered in (
            on_blocks(lambda p: scols.read_cols(p, (71, 67, 68, 69),
                                                "pallas"),
                      (by_rows,), by_lanes).lower(payload),
            on_blocks(lambda p, v: scols.write_cols(p, (72, 73), v,
                                                    "pallas"),
                      (by_rows, by_lanes), by_rows).lower(
                          payload, shape((2, rows), by_lanes)),
            on_blocks(lambda p, d, s, o: scols.add_scaled(
                p, d, (71, 71), 74, s, o, "pallas"),
                      (by_rows, PS(), PS(), PS()), by_rows).lower(
                          payload, shape((), PS(), jnp.int32),
                          shape((), PS()), shape((), PS(), jnp.bool_))):
        text = lowered.compile().as_text()
        assert "tpu_custom_call" in text
        assert "all-gather" not in text and "all-to-all" not in text
