"""The plain references agree with the program at its REDUCED sizes on
the CPU: logits at every exit from the model's forward, and the masked
serving step's difficulty, exit index, class and confidence."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check
from bench.reference import resnet, vit

TAU = np.asarray([0.55, 0.45, 0.35], np.float32)
BETA = 0.3


def _cfg(pcfg, family):
    d = {k: list(v) if isinstance(v, tuple) else v
         for k, v in dataclasses.asdict(pcfg).items()
         if isinstance(v, (int, float, str, bool, tuple))}
    d.update(family=family, logit_std=6.0, branch_scale=0.2,
             exit_feature_rms=[1.0] * pcfg.n_exits, n_exits=pcfg.n_exits,
             difficulty={"tau_edge": 0.1, "var_scale": 0.05,
                         "grad_scale": 0.2, "w": [0.4, 0.3, 0.3]})
    return d


@pytest.mark.parametrize("arch,family,ref", [("resnet-152", "resnet", resnet),
                                             ("vit-h14", "vit", vit)])
def test_reference_matches_the_masked_step(arch, family, ref):
    from repro.configs import registry
    from repro.core.routing import DartParams
    from repro.engine import DartEngine
    from repro.launch.mesh import make_serving_mesh
    from repro.models import get_family
    from bench.images import clutter_images

    pcfg = registry.get_reduced(arch)
    cfg = _cfg(pcfg, family)
    e = pcfg.n_exits
    tau = TAU[:e - 1]
    params = ref.init(jax.random.key(3), cfg, jnp.float32)
    x = clutter_images(jax.random.key(4), 8, pcfg.img_res)

    want = check.reference_logits(ref, cfg, params, x)
    got = np.asarray(get_family(pcfg).forward(params, jnp.asarray(x),
                                              pcfg)["exit_logits"])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    engine = DartEngine.from_config(
        pcfg, params, dart=DartParams(tau=jnp.asarray(tau),
                                      coef=jnp.ones(e - 1), beta_diff=BETA),
        mesh=make_serving_mesh(), adapt=False, buckets=(8,))
    out = {k: np.asarray(v) for k, v in engine.infer(x, mode="masked").items()
           if k in ("exit_idx", "pred", "conf", "alpha")}
    alpha = check.reference_alpha(cfg, x)
    np.testing.assert_allclose(out["alpha"], alpha, atol=1e-5)
    ex, pred, conf = check.decide(want, alpha, tau, BETA)
    np.testing.assert_array_equal(out["exit_idx"], ex)
    np.testing.assert_array_equal(out["pred"], pred)
    np.testing.assert_allclose(out["conf"], conf, atol=1e-5)
    assert all(v < 1e-4 for v in check.compare(out, want, alpha, tau,
                                               BETA).values())
