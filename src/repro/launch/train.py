"""End-to-end training driver: config -> mesh -> sharded state -> resilient
loop (checkpoint/restart, straggler detection) -> metrics.

Single-host usage (CPU tests / examples):
    PYTHONPATH=src python -m repro.launch.train --arch gpt2-124m --smoke \
        --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

On a fleet, the same entrypoint runs once per host (jax.distributed
initializes from the cluster env); the data pipeline is stateless-by-step so
restarts and elastic resizes replay exactly (see distributed/fault_tolerance).
"""

from __future__ import annotations

import argparse
import dataclasses
import logging
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

import repro.configs as configs
from repro.checkpoint import CheckpointStore
from repro.configs.base import ShapeConfig
from repro.data import pipeline
from repro.distributed import sharding as shard_rules
from repro.distributed.fault_tolerance import FaultToleranceConfig, ResilientLoop
from repro.launch.mesh import make_host_mesh
from repro.optim import adamw
from repro.train import steps as steps_mod

log = logging.getLogger("repro.train")


@dataclasses.dataclass
class TrainJob:
    arch: str = "gpt2-124m"
    smoke: bool = True
    steps: int = 50
    batch: int = 8
    seq: int = 128
    lr: float = 3e-4
    microbatches: int = 1
    remat: str = "none"
    zero: bool = True
    seed: int = 0
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 20
    model_axis: int = 1
    log_every: int = 10


def build_state(job: TrainJob, mesh):
    cfg = (configs.get_smoke_config(job.arch) if job.smoke
           else configs.get_config(job.arch))
    shape = ShapeConfig("train_job", job.seq, job.batch, "train")
    opt_cfg = adamw.AdamWConfig(lr=job.lr, total_steps=max(job.steps, 1))
    run = steps_mod.RunConfig(remat=job.remat, microbatches=job.microbatches,
                              zero=job.zero, opt=opt_cfg)
    params = steps_mod.init_model(jax.random.PRNGKey(job.seed), cfg)
    p_sh = shard_rules.param_shardings(params, mesh)
    params = jax.device_put(params, p_sh)
    opt = adamw.init_opt_state(params, run.opt)
    o_sh = shard_rules.opt_state_shardings(params, p_sh, mesh, zero=run.zero)
    return cfg, shape, run, {"params": params, "opt": opt}, p_sh


def train(job: TrainJob) -> Dict[str, Any]:
    mesh = make_host_mesh(job.model_axis)
    cfg, shape, run, state, p_sh = build_state(job, mesh)
    data_cfg = pipeline.DataConfig(seed=job.seed)
    train_step = jax.jit(steps_mod.make_train_step(cfg, run),
                         donate_argnums=(0, 1))
    metrics_hist = []

    def step_fn(step: int, state):
        batch = pipeline.global_batch(cfg, shape, data_cfg, step)
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        with mesh:
            params, opt, metrics = train_step(state["params"], state["opt"], batch)
        if step % job.log_every == 0 or step == job.steps - 1:
            m = {k: float(v) for k, v in metrics.items()}
            m["step"] = step
            metrics_hist.append(m)
            log.info("step %d loss %.4f gnorm %.3f", step, m["loss"], m["grad_norm"])
        return {"params": params, "opt": opt}

    if job.ckpt_dir:
        store = CheckpointStore(job.ckpt_dir)
        ft = FaultToleranceConfig(checkpoint_every=job.ckpt_every, async_save=True)
        loop = ResilientLoop(store, ft, step_fn,
                             lambda: build_state(job, mesh)[3])
        out = loop.run(job.steps)
        state = out["state"]
        result = {"restarts": out["restarts"],
                  "straggler_events": out["straggler_events"]}
    else:
        for step in range(job.steps):
            state = step_fn(step, state)
        result = {"restarts": 0, "straggler_events": 0}

    result.update({
        "final_metrics": metrics_hist[-1] if metrics_hist else {},
        "history": metrics_hist,
        "state": state,
        "cfg": cfg,
    })
    return result


def main(argv=None) -> int:
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
    ap = argparse.ArgumentParser()
    for f in dataclasses.fields(TrainJob):
        name = "--" + f.name.replace("_", "-")
        if f.type == "bool" or isinstance(f.default, bool):
            ap.add_argument(name, action="store_true", default=f.default)
        else:
            ap.add_argument(name, type=type(f.default) if f.default is not None else str,
                            default=f.default)
    args = ap.parse_args(argv)
    job = TrainJob(**{f.name: getattr(args, f.name) for f in dataclasses.fields(TrainJob)})
    t0 = time.time()
    out = train(job)
    print(f"done in {time.time()-t0:.1f}s: {out['final_metrics']}")
    return 0


if __name__ == "__main__":
    # the command line only: tests that call main() leave the cache off
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
