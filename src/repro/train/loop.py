"""Training loop: restartable, checkpointed, metric-logged.

Composes: model (repro.models) + optimizer (AdamW/WSD) + deterministic data
(data.lm_data) + checkpoint-restart supervision (distributed.fault_tolerance)
+ optional sharding over a mesh.  Used by launch/train.py and the examples.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..data.lm_data import TokenStream
from ..distributed.fault_tolerance import FailureInjector, RestartableRunner
from .optimizer import AdamWConfig
from .train_state import init_train_state, make_train_step

__all__ = ["TrainLoopConfig", "train"]

# Compute/communication overlap (async collectives) for the step's compile
# on a TPU.  They go in as compile options, not as XLA_FLAGS: XLA reads
# XLA_FLAGS once, when the backend starts, before it can be asked what it
# is.  Every key must be one libtpu knows, or the compile fails
# (tests/test_tpu_compile.py compiles with them for a described v5e).
_TPU_OPTIONS = {
    "xla_tpu_enable_async_collective_fusion": "true",
    "xla_tpu_enable_async_collective_fusion_fuse_all_gather": "true",
    "xla_tpu_overlap_compute_collective_tc": "true",
    "xla_enable_async_all_gather": "true",
}


@dataclasses.dataclass
class TrainLoopConfig:
    n_steps: int = 100
    ckpt_root: str = "/tmp/repro_ckpt"
    ckpt_every: int = 50
    grad_accum: int = 1
    seed: int = 0
    log_every: int = 10
    opt: AdamWConfig = dataclasses.field(default_factory=AdamWConfig)


def train(model, shape, loop_cfg: TrainLoopConfig,
          injector: Optional[FailureInjector] = None,
          mesh=None, batch_shardings=None,
          on_metrics: Optional[Callable] = None) -> Dict:
    cfg = model.cfg
    extra = {}
    if cfg.frontend == "vision_patches":
        extra["patch_embeds"] = ((cfg.n_frontend_tokens, cfg.d_model),
                                 np.float32)
    if cfg.is_encdec:
        src = max(1, int(shape.seq_len * cfg.encoder_len_ratio))
        extra["src_embeds"] = ((src, cfg.d_model), np.float32)
    text_len = shape.seq_len - (cfg.n_frontend_tokens
                                if cfg.frontend == "vision_patches" else 0)
    stream = TokenStream(cfg.vocab_size, text_len, shape.global_batch,
                         seed=loop_cfg.seed, extra_specs=extra)

    step_fn = make_train_step(model, loop_cfg.opt,
                              grad_accum=loop_cfg.grad_accum)
    jit_step = jax.jit(step_fn, donate_argnums=(0,),
                       compiler_options=_TPU_OPTIONS
                       if jax.default_backend() == "tpu" else None)

    losses = []

    def init_state():
        return init_train_state(model, jax.random.PRNGKey(loop_cfg.seed))

    def one_step(state, step):
        batch = {k: jnp.asarray(v) for k, v in stream.batch(step).items()}
        state, metrics = jit_step(state, batch)
        return state, metrics

    def metrics_hook(step, metrics):
        if step % loop_cfg.log_every == 0 or step == loop_cfg.n_steps:
            loss = float(metrics["loss"])
            losses.append((step, loss))
            print(f"  step {step:5d} loss {loss:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f}")
        if on_metrics:
            on_metrics(step, metrics)

    runner = RestartableRunner(loop_cfg.ckpt_root,
                               ckpt_every=loop_cfg.ckpt_every)
    t0 = time.time()
    stats = runner.run(init_state, one_step, loop_cfg.n_steps,
                       injector=injector, on_metrics=metrics_hook)
    stats["wall_s"] = time.time() - t0
    stats["losses"] = losses
    return stats
