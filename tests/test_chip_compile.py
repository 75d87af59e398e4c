"""The lease-plane window kernels compile for a TPU v5e (``interpret=False``)
at the main path's block geometry: block_n=512, window=16.

The chip is described, not attached (``topologies.get_topology_desc``), so
these tests run Mosaic and the TPU compiler without a device and catch
what interpret mode cannot: unaligned slices, layouts the chip refuses,
and more VMEM than a kernel may use. The topology is described inside a
module fixture only, never at import: one process at a time may load the
TPU library, and every pytest worker imports this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.hlo import kernel_scoped_vmem
from repro.lease_array.kernel import (
    delayed_kernel_args,
    lease_window_delayed_pallas,
    lease_window_sync_pallas,
)
from repro.lease_array.state import PackedLeaseState

A, P, N, T = 5, 8, 4096, 64
BLOCK_N, WINDOW = 512, 16
#: v5e's default scoped-VMEM limit; a kernel above it needs a raised limit
SCOPED_VMEM_LIMIT = 16 << 20


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # the TPU library logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _sync(sds):
    packed = PackedLeaseState(sds(A, N), sds(A, N), sds(1, N), sds(1, N))
    fn = functools.partial(
        lease_window_sync_pallas, majority=3, lease_q4=97, n_proposers=P,
        block_n=BLOCK_N, window=WINDOW, interpret=False,
    )
    args = (packed, sds(), sds(T, N), sds(T, N), sds(T, A), sds(T, P),
            sds(T, A))
    return fn, args


def _delayed(sds, *, extend_restart=False, skip_stable=True):
    args, streams = delayed_kernel_args(
        A, N, P, T, extend=extend_restart, restart=extend_restart,
        sharding=sds().sharding,
    )

    def fn(args, streams):
        return lease_window_delayed_pallas(
            *args, **streams, majority=3, lease_q4=97, round_q4=36,
            n_proposers=P, block_n=BLOCK_N, window=WINDOW, interpret=False,
            skip_stable=skip_stable,
        )

    return fn, (args, streams)


def _placed(sds):
    """A placement's restart table in place of the restart columns: three
    replicas, slot k's acceptor and proposer on one node (A == P)."""
    args, streams = delayed_kernel_args(
        3, N, 3, T, extend=True, restart=True, placed=True,
        sharding=sds().sharding,
    )

    def fn(args, streams):
        return lease_window_delayed_pallas(
            *args, **streams, majority=2, lease_q4=113, round_q4=20,
            n_proposers=3, block_n=BLOCK_N, window=WINDOW, interpret=False,
            deaf_q4=113,
        )

    return fn, (args, streams)


VARIANTS = {
    "sync": _sync,
    "delayed_honest": _delayed,
    "delayed_extend_restart": functools.partial(
        _delayed, extend_restart=True
    ),
    "delayed_no_skip_stable": functools.partial(_delayed, skip_stable=False),
    "delayed_placed": _placed,
}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_window_kernel_compiles_for_v5e(one_chip, variant):
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    fn, args = VARIANTS[variant](sds)
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text  # the Mosaic kernel, not an XLA loop
    vmem = kernel_scoped_vmem(text)
    assert len(vmem) == 1 and 0 < vmem[0] <= SCOPED_VMEM_LIMIT
    mem = compiled.memory_analysis()
    # every [T, N] stream is a kernel argument; the owners/counts outputs
    # are [T, N] each
    assert mem.output_size_in_bytes >= 2 * T * N * 4
