"""Elastic remesh: resume training on a DIFFERENT device mesh.

The PyWren property applied to distributed training: because ALL durable
state lives in storage and steps are stateless, scaling the mesh is just
checkpoint -> re-place on the new mesh -> continue.  This script runs on 8
fake host devices: trains on a (4 data x 2 model) mesh, checkpoints,
reloads the same run on (2 data x 4 model), and keeps training — losses
continue smoothly across the remesh.

Run:  python examples/elastic_remesh.py     (sets its own XLA_FLAGS)
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro.configs import CONFIGS
from repro.data import DataConfig, synthetic_batch
from repro.launch.mesh import make_mesh
from repro.launch.shardings import state_pspec, to_shardings
from repro.storage import ObjectStore
from repro.train import TrainState, adamw, init_train_state, make_train_step
from repro.train import checkpoint as ck


def place(state, mesh):
    sh = to_shardings(mesh, state_pspec(mesh, state))
    return jax.tree_util.tree_map(lambda x, s: jax.device_put(x, s), state, sh)


def run_steps(state, cfg, opt, dcfg, mesh, start, n):
    step = jax.jit(make_train_step(cfg, opt))
    losses = []
    with jax.set_mesh(mesh):
        state = place(state, mesh)
        for i in range(start, start + n):
            state, m = step(state, synthetic_batch(dcfg, i, cfg))
            losses.append(float(m["loss"]))
    return state, losses


def main() -> None:
    cfg = dataclasses.replace(
        CONFIGS["llama3-8b"].reduced(), n_layers=2, d_model=128, d_ff=256,
        n_heads=4, n_kv_heads=4, head_dim=32, vocab_size=512,
    )
    opt = adamw(3e-3, weight_decay=0.0)
    dcfg = DataConfig(seq_len=32, global_batch=8, vocab_size=cfg.vocab_size)
    store = ObjectStore()

    mesh_a = make_mesh(dp=4, tp=2)
    state = init_train_state(cfg, opt, jax.random.PRNGKey(0))
    state, losses_a = run_steps(state, cfg, opt, dcfg, mesh_a, 0, 10)
    ck.save(store, "remesh", 1, tuple(state), meta={"step": 10})
    print(f"mesh (4x2): losses {losses_a[0]:.3f} -> {losses_a[-1]:.3f}")

    # ---- elastic remesh: reload the run on a different mesh --------------
    mesh_b = make_mesh(dp=2, tp=4)
    loaded, meta, _ = ck.load(store, "remesh")
    state_b = TrainState(*loaded)
    state_b, losses_b = run_steps(state_b, cfg, opt, dcfg, mesh_b, meta["step"], 10)
    print(f"mesh (2x4): losses {losses_b[0]:.3f} -> {losses_b[-1]:.3f}")
    assert losses_b[0] < losses_a[0], "training must continue, not restart"
    print("remesh resume OK: storage-resident state + stateless steps "
          "(the PyWren contract) make mesh shape a per-task detail")


if __name__ == "__main__":
    main()
