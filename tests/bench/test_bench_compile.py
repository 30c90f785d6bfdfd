"""Described-v5e compile of the yi-6b cells' decode step (ACCEL build,
32 slots x 4096 positions, 16 layers at published widths): the program
the chip runs every decode step must compile natively and fit one
v5e's memory with its pool.  Nothing runs.

The topology is described inside a module fixture (the TPU library
loads there, in the worker that runs this file, never at import time),
and the persistent compilation cache is off around the compile: an
entry written for a described chip cannot be read back without one.
"""
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import _paths  # noqa: F401
from bench import cells, serve


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def test_yi6b_decode_step_compiles_and_fits(one_chip, monkeypatch):
    from repro.kernels import ops
    from repro.models.model import build_model
    from repro.models.sampling import sampling_leaves
    from repro.serve.api import SamplingParams
    monkeypatch.setenv(ops.INTERPRET_ENV, "0")     # lower Pallas natively
    conf = json.loads((cells.ROOT / "bench/configs/yi-6b.json").read_text())
    eng = conf["engine"]
    B, BS = eng["max_slots"], eng["block_size"]
    nbt = eng["max_seq"] // BS
    model = build_model(serve.model_config("yi-6b", cells.model_dims(conf)),
                        None)
    S = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    params = jax.tree.map(S, jax.eval_shape(model.init,
                                            jax.random.PRNGKey(0)))
    cache = jax.tree.map(S, jax.eval_shape(
        lambda: model.init_paged_cache(B * nbt + 1, BS, lane_align=True)))
    batch = {"tokens": S(jnp.zeros((B, 1), jnp.int32)),
             "index": S(jnp.zeros((B,), jnp.int32)),
             "block_table": S(jnp.zeros((B, nbt), jnp.int32)),
             **{k: S(v) for k, v in sampling_leaves(SamplingParams(),
                                                    B).items()}}
    step = jax.jit(lambda p, c, b: model.decode_sampled(p, c, b,
                                                        backend="pallas"),
                   donate_argnums=(1,))
    compiled = step.lower(params, cache, batch).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().peak_memory_in_bytes < 16e9
