"""AdamW with decoupled weight decay, fp32 moments, global-norm clipping.

Pure pytree implementation (no optax dependency).  Moments are kept in
float32 regardless of parameter dtype (mixed-precision training); the
ZeRO-1 sharding of the moment pytree is an annotation applied by
``repro.dist.sharding.zero1_spec`` at pjit time, not a property of the
math here.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["AdamWConfig", "OptState", "init_opt", "apply_updates",
           "global_norm", "clip_by_global_norm"]


@dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    # parameters whose path matches this regex get no weight decay
    no_decay_pattern: str = r"(bias|scale|norm|A_log|D$|dt_bias)"


class OptState(NamedTuple):
    step: jnp.ndarray          # ()
    mu: Any                    # first moments  (fp32 pytree)
    nu: Any                    # second moments (fp32 pytree)


def init_opt(params: Any) -> OptState:
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return OptState(step=jnp.zeros((), jnp.int32), mu=zeros,
                    nu=jax.tree.map(jnp.copy, zeros))


def global_norm(tree: Any) -> jnp.ndarray:
    sq = sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
             for x in jax.tree.leaves(tree))
    return jnp.sqrt(sq)


def clip_by_global_norm(grads: Any, max_norm: float) -> Tuple[Any, jnp.ndarray]:
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale), grads), norm


def apply_updates(cfg: AdamWConfig, params: Any, grads: Any, state: OptState,
                  lr_scale: jnp.ndarray | float = 1.0,
                  decay_mask: Optional[Any] = None
                  ) -> Tuple[Any, OptState, Dict[str, jnp.ndarray]]:
    """One AdamW step.  grads may be any dtype; math runs in fp32 and
    parameters are cast back to their storage dtype."""
    import re
    if decay_mask is None:
        pat = re.compile(cfg.no_decay_pattern)
        paths = jax.tree_util.tree_map_with_path(
            lambda kp, _: jax.tree_util.keystr(kp, simple=True,
                                               separator="/"), params)
        decay_mask = jax.tree.map(lambda p: 0.0 if pat.search(p) else 1.0, paths)

    grads, gnorm = clip_by_global_norm(grads, cfg.clip_norm)
    step = state.step + 1
    b1c = 1.0 - cfg.b1 ** step.astype(jnp.float32)
    b2c = 1.0 - cfg.b2 ** step.astype(jnp.float32)
    lr = cfg.lr * lr_scale

    def upd(p, g, m, v, wd):
        m = cfg.b1 * m + (1.0 - cfg.b1) * g
        v = cfg.b2 * v + (1.0 - cfg.b2) * jnp.square(g)
        mhat = m / b1c
        vhat = v / b2c
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps)
        pf = p.astype(jnp.float32)
        pf = pf - lr * (delta + cfg.weight_decay * wd * pf)
        return pf.astype(p.dtype), m, v

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(state.mu)
    flat_v = jax.tree.leaves(state.nu)
    flat_w = jax.tree.leaves(decay_mask)
    out = [upd(p, g, m, v, w) for p, g, m, v, w in
           zip(flat_p, flat_g, flat_m, flat_v, flat_w)]
    new_p = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_v = treedef.unflatten([o[2] for o in out])
    return new_p, OptState(step, new_m, new_v), {"grad_norm": gnorm}
