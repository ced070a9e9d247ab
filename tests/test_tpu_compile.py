"""The segment kernels compile for a TPU v5e that is described, not
attached: Mosaic's verdict on each kernel as it stands, at the real
shapes of the Higgs cell (10.5M x 128 lanes) and of the Epsilon cell
(409,600 x 2,048 lanes, 2,000 columns x 64 bins), with no chip.  `test_pallas_segment.py` runs
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

ROWS, LANES, FEATURES, BINS = 10_502_408, 128, 28, 256


@pytest.fixture(scope="module")
def one_chip():
    import os
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
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
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


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
    return payload, payload, i32, i32, pred, f32, f32, features + 3, bins


@pytest.mark.parametrize("lanes,bins", [(LANES, BINS), (LANES, 255),
                                        (256, 255), (384, 64),
                                        (LANES, 1024)])
def test_partition_acc_compiles_for_v5e(one_chip, lanes, bins):
    """The accumulator partition at the Higgs cell's shape, at its 255
    bins (eight words of packed bitset, the last short of a bit) and past
    128 lanes, where the split column's window is a lane slice of the ring
    at a traced offset (two chunks a trip at 256 lanes, one at 384; as
    many rows as fill the chip the same), and at 1,024 bins, where the
    column is read out at HIGHEST.
    Pass A's index arithmetic has rows in lanes: the NT product that
    reads the column out, the vector shifts of the categorical test and
    the sublane broadcast into the one-hot are Mosaic's to accept."""
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
