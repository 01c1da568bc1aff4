"""chip_smoke.py on the CPU: its phases at REDUCED size with the kernels
in interpret mode, its refusal to run anywhere but on a TPU, and the
compile-cache helper every entry point calls."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

from repro.configs import registry
from repro.kernels import dispatch
from repro.launch import cache

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _load_script():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def interpret_kernels(monkeypatch):
    """Every dispatch resolves to the interpreted kernels, the server's
    own dispatch thread included (``force_backend`` is per thread; the
    environment variable is seen by all of them)."""
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas-interpret")
    dispatch.reset_dispatch_counts()
    with dispatch.force_backend("pallas-interpret"):
        yield


def _check(cs, report, kernels):
    counts = dispatch.dispatch_counts()
    assert cs.check_report(report, counts, kernels,
                           expect="pallas-interpret") == [], (report, counts)
    assert report["answered"] == report["requests"]


def test_classifier_phase_reduced(interpret_kernels):
    cs = _load_script()
    from repro.launch.mesh import make_serving_mesh
    report = cs.phase_classifier(registry.get_reduced("resnet-152"), seed=0,
                                 mesh=make_serving_mesh(), n_requests=8,
                                 sizes=(1, 3, 8))
    _check(cs, report, cs.CLASSIFIER_KERNELS)
    assert report["served_per_replica"] == [report["samples"]]


def test_lm_phase_reduced(interpret_kernels):
    cs = _load_script()
    """More prompts than slots, of mixed lengths: slots and pages are
    freed and reused while other prompts decode."""
    cs = _load_script()
    report = cs.phase_lm(registry.get_reduced("tinyllama-1.1b"), seed=0,
                         n_prompts=6, new_tokens=(3, 5), n_slots=2,
                         prompt_len=6, page_size=4, noise=(2, 3))
    _check(cs, report, cs.LM_KERNELS)
    assert 6 * 3 <= report["tokens"] <= 6 * 5
    assert report["agree"] == report["tokens"], report["disagree"]


def test_quantile_tau_gives_every_exit_a_share():
    import numpy as np
    cs = _load_script()
    rs = np.random.RandomState(0)
    conf, alpha = rs.uniform(0.1, 1.0, (3, 64)), rs.uniform(0, 1, 64)
    tau = cs.quantile_tau(conf, alpha, 3)
    alive = np.ones(64, bool)
    taken = []
    for s in range(2):
        fire = alive & (conf[s] - cs.BETA_DIFF * alpha > tau[s])
        taken.append(int(fire.sum()))
        alive &= ~fire
    taken.append(int(alive.sum()))
    assert min(taken) > 0, taken


@pytest.mark.parametrize("scale", [0.5, 6.0, 40.0])
def test_ulp_band_is_one_ulp_on_every_logit(scale):
    """The band equals the confidence move when the top logit gains one
    bf16 ulp and every other logit loses one, in either direction."""
    import numpy as np
    cs = _load_script()
    z = np.random.RandomState(1).normal(0, scale, 1000)
    top = z.max()
    ulp = 2.0 ** (np.floor(np.log2(abs(top))) - 7)
    assert float(jnp_bf16_spacing(top)) == ulp

    def conf(x):
        e = np.exp(x - x.max())
        return e.max() / e.sum()

    p = conf(z)
    is_top = z == top
    moves = [abs(conf(np.where(is_top, z + d, z - d)) - p)
             for d in (ulp, -ulp)]
    assert np.isclose(cs.ulp_band(p, top), max(moves), rtol=1e-6)


def jnp_bf16_spacing(x):
    """bf16 spacing at ``x`` as JAX rounds it."""
    import jax.numpy as jnp
    b = jnp.asarray(x, jnp.bfloat16)
    return jnp.abs(jnp.nextafter(b, jnp.asarray(jnp.inf, jnp.bfloat16))
                   - b).astype(jnp.float32)


def _run_script(cwd, env_extra, drop=()):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(env_extra)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=300)
    return r.returncode, json.loads(r.stdout.strip().splitlines()[-1])


def test_script_refuses_cpu(tmp_path):
    rc, last = _run_script(ROOT, {"JAX_PLATFORMS": "cpu",
                                  cache.ENV: str(tmp_path)})
    assert rc != 0
    assert last["ok"] is False and last["device"]["platform"] == "cpu"


def test_script_alone_fails(tmp_path):
    shutil.copy(SCRIPT, tmp_path)
    rc, last = _run_script(tmp_path, {"JAX_PLATFORMS": "cpu"},
                           drop=("PYTHONPATH",))
    assert rc != 0
    assert last == {"ok": False, "device": None}


# ---------------------------------------------------------------------------
# compile cache
# ---------------------------------------------------------------------------

def test_compile_cache_dir(monkeypatch, tmp_path):
    monkeypatch.setenv(cache.ENV, str(tmp_path))
    assert cache.compile_cache_dir() == str(tmp_path)
    monkeypatch.delenv(cache.ENV)
    fixed = os.path.join(ROOT, ".jax_cache")
    assert cache.compile_cache_dir() == fixed
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


CACHE_SCRIPT = """
import jax, jax.numpy as jnp
from repro.launch.cache import enable_compile_cache
print(enable_compile_cache())
jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
"""


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_entries_land_in_one_place(tmp_path, env_set):
    """Cache entries appear where JAX_COMPILATION_CACHE_DIR says, else
    under the checkout's fixed ``.jax_cache``, and nowhere else.  The
    helper runs from a copy so the fixed path is the copy's own."""
    checkout = tmp_path / "checkout"
    dst = checkout / "src" / "repro" / "launch"
    dst.mkdir(parents=True)
    shutil.copy(cache.__file__, dst / "cache.py")
    env = {k: v for k, v in os.environ.items() if k != cache.ENV}
    env.update(PYTHONPATH=str(checkout / "src"), JAX_PLATFORMS="cpu")
    named = tmp_path / "named"
    if env_set:
        env[cache.ENV] = str(named)
    r = subprocess.run([sys.executable, "-c", CACHE_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    fixed = checkout / ".jax_cache"
    want, other = (named, fixed) if env_set else (fixed, named)
    assert r.stdout.strip().splitlines()[-1] == str(want)
    assert want.is_dir() and any(want.iterdir())
    assert not other.exists()
