"""Compile the tagger serving path for a TPU v5e that is described, not
attached: the Mosaic compiler refuses here what it would refuse on the chip
(block shapes off the (8, 128) tiling, unaligned lane stores, matmul
operand types), at no chip time.

Each case lowers the whole tagger forward (``rnn_tagger.forward``, the
program the serving engine compiles) at the paper's full widths with
``backend="pallas_tpu"`` — spelled out, because on this CPU platform
``"auto"`` resolves to the interpreter — and asserts that a compiled Mosaic
kernel (``tpu_custom_call``) is in the program.  A compile is not a run:
numerics and times come only from the chip (``chip_smoke.py``).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import FixedPointConfig
from repro.configs import flavor_tagging, quickdraw
from repro.kernels.schedule import KernelSchedule
from repro.models import build_model, rnn_tagger

TPU = dict(backend="pallas_tpu")

CASES = {
    "flavor-lstm-static-R1": (flavor_tagging.lstm_config(),
                              KernelSchedule(**TPU), 128, None),
    "flavor-gru-static-R1": (flavor_tagging.gru_config(),
                             KernelSchedule(**TPU), 128, None),
    "quickdraw-lstm-static-R1": (quickdraw.lstm_config(),
                                 KernelSchedule(**TPU), 128, None),
    "quickdraw-gru-static-R1": (quickdraw.gru_config(),
                                KernelSchedule(**TPU), 128, None),
    "quickdraw-lstm-static-R4": (quickdraw.lstm_config(),
                                 KernelSchedule(reuse_factor=4, **TPU),
                                 128, None),
    "quickdraw-lstm-pipeline-R4": (quickdraw.lstm_config(),
                                   KernelSchedule(reuse_factor=4,
                                                  mode="pipeline", **TPU),
                                   128, None),
    "flavor-lstm-int8": (flavor_tagging.lstm_config(),
                         KernelSchedule(**TPU), 128, FixedPointConfig(8, 3)),
    "flavor-lstm-batch1": (flavor_tagging.lstm_config(),
                           KernelSchedule(**TPU), 1, None),
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable for a described chip cannot be read back without one:
    # keep these compiles out of any persistent compilation cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("case", sorted(CASES))
def test_tagger_compiles_for_v5e(case, one_chip):
    cfg, schedule, batch, fp = CASES[case]
    params = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        params)
    r = cfg.rnn
    x = jax.ShapeDtypeStruct((batch, r.seq_len, r.input_size), jnp.float32,
                             sharding=one_chip)
    fwd = jax.jit(lambda p, x: rnn_tagger.forward(
        cfg, p, x, impl="pallas", schedule=schedule, fp=fp))
    text = fwd.lower(params, x).compile().as_text()
    assert "tpu_custom_call" in text
