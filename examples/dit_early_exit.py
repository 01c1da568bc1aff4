"""DART on a diffusion transformer: early-exit denoising (DESIGN.md §3)
through the engine's pluggable strategies.

A small DiT is trained with per-exit ε-heads (Eq. 18 with MSE); DDIM
sampling then exits each step at the earliest CONVERGED head, gated by the
latent+timestep difficulty.  The exit criterion and difficulty estimator
are the registered ``diffusion-convergence`` / ``latent`` strategies —
the same engine that serves classifiers routes diffusion exits.
High-noise (early) steps are easy — expect shallow exits there and
deeper exits near the end of the trajectory.

Run:  PYTHONPATH=src python examples/dit_early_exit.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.routing import DartParams
from repro.data.datasets import DatasetConfig
from repro.engine import DartEngine
from repro.models.dit import (DiTConfig, dit_forward, cosine_alpha_bar)
from repro.runtime.trainer import Trainer, TrainConfig

CFG = DiTConfig(name="dit-demo", img_res=64, patch=2, n_layers=4,
                d_model=64, n_heads=4, n_classes=10, exit_layers=(0, 1),
                remat=False)
DATA = DatasetConfig(name="latents", img_res=64, n_train=1024)


def main():
    print("training 4-layer DiT with exits after layers 0 and 1 ...")
    tr = Trainer(CFG, TrainConfig(batch_size=16, steps=200, lr=1e-3,
                                  log_every=30), DATA, data_kind="latents")
    tr.run()
    print("loss:", [round(h["loss"], 3) for h in tr.history])

    engine = DartEngine.from_config(
        CFG, tr.params,
        dart=DartParams(tau=jnp.asarray([0.93, 0.93]), coef=jnp.ones(2),
                        beta_diff=0.05),
        confidence="diffusion-convergence", difficulty="latent",
        adapt=False)
    abar = cosine_alpha_bar()
    b = 8
    key = jax.random.key(0)
    xt = jax.random.normal(key, (b, 8, 8, 4))
    y = jnp.arange(b) % 10
    steps = np.linspace(999, 120, 25).astype(int)  # stop above the low-noise regime: the demo model is tiny/undertrained and its x0-estimates blow up as abar->1

    @jax.jit
    def denoise(xt, t, t_prev, y):
        out = dit_forward(tr.params, xt, t, y, CFG)
        eps_stack = jnp.stack([e[..., :4] for e in out["exit_eps"]])
        routed = engine.route(eps_stack, xt, signal_frac=jnp.sqrt(abar[t]))
        eps = jnp.take_along_axis(
            eps_stack, routed["exit_idx"][None, :, None, None, None],
            axis=0)[0]
        at = abar[t][:, None, None, None]
        ap = abar[t_prev][:, None, None, None]
        x0 = (xt - jnp.sqrt(1 - at) * eps) / jnp.sqrt(at)
        return jnp.sqrt(ap) * x0 + jnp.sqrt(1 - ap) * eps, routed["exit_idx"]

    print("\nsampler_step,t,mean_exit_depth")
    depth_by_phase = {"early(noisy)": [], "late(clean)": []}
    for i, t in enumerate(steps):
        t_prev = steps[i + 1] if i + 1 < len(steps) else 0
        tb = jnp.full((b,), t)
        xt, exit_idx = denoise(xt, tb, jnp.full((b,), t_prev), y)
        d = float(jnp.mean(exit_idx))
        phase = "early(noisy)" if t > 500 else "late(clean)"
        depth_by_phase[phase].append(d)
        if i % 5 == 0:
            print(f"{i},{t},{d:.2f}")
    print("\nmean exit depth  early(noisy):",
          round(float(np.mean(depth_by_phase['early(noisy)'])), 3),
          " late(clean):",
          round(float(np.mean(depth_by_phase['late(clean)'])), 3))
    print("latent stats after sampling: mean",
          float(jnp.mean(xt)), "std", float(jnp.std(xt)))


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
