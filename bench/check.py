"""Whether what the timed path served is correct, against the plain
float32 reference.

After the window, a sample of the answered requests, drawn from the
seed and always holding a largest request, is run through the family's
reference (``bench/reference/<family>.py``) and DART's reference gate
(``bench/reference/gate.py``), in blocks of ``BLOCK`` rows.  Five numbers
are compared, each the worst over the sampled rows but one:

* ``pred_gap``: how far the served class's reference logit lies below
  the reference's best, at the exit that served the row (0 when the
  classes agree);
* ``conf_err``: |served confidence - reference confidence| there;
* ``conf_err_mean``: the same, averaged over the sampled rows: a
  bfloat16 near-tie moves one row's confidence as far as a coarser
  backbone moves many, so only the mean tells the two apart;
* ``gate_violation``: how far the reference's confidence lies on the
  wrong side of its Eq. 19 threshold for the served exit decision: above
  it at an earlier exit, or below it at the serving exit (0 when the
  reference decides the same way);
* ``alpha_err``: |served Eq. 8 difficulty - reference difficulty|.

Each has a limit per cell (``bench/limits/<cell>.json``), set from what
sound runs of the program read and what the fp8 control reads
(PERF.md): the reference on fp8 operands, the difficulty on fp8 images
in bfloat16 arithmetic.  A number whose control reading does not reach three times
the program's has the limit null: it is reported, not compared.  An
answer that never came, or failed for any reason but backpressure,
makes the run incorrect as well.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import gate
from bench.reference.fp8 import fake_quant

BLOCK = 16
NUMBERS = ("pred_gap", "conf_err", "conf_err_mean", "gate_violation",
           "alpha_err")
#: failures that are refusals under load, not wrong answers
REFUSALS = ("RequestShed", "RequestRejected")


def sample(reqs, seed: int, rows: int):
    """Answered requests to check: one of the largest, then others in
    an order drawn from ``seed``, until ``rows`` rows are held."""
    done = [r for r in reqs if r.result is not None]
    if not done:
        return []
    rng = np.random.default_rng([seed, 3])
    order = list(rng.permutation(len(done)))
    big = max(range(len(done)), key=lambda i: (done[i].n, -order.index(i)))
    out, n = [done[big]], done[big].n
    for i in order:
        if n >= rows:
            break
        if i != big:
            out.append(done[i])
            n += done[i].n
    return out


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _reference_block(params, images, family, cfg_items, fp8):
    return family.forward(params, images, dict(cfg_items), fp8)


def reference_logits(family, cfg, params, images, fp8=False):
    """(E, N, C) float32 reference logits, ``BLOCK`` rows at a time."""
    items = tuple((k, tuple(v) if isinstance(v, list) else v)
                  for k, v in sorted(cfg.items())
                  if isinstance(v, (int, float, str, list)))
    out = []
    for a in range(0, len(images), BLOCK):
        blk = images[a:a + BLOCK]
        pad = np.zeros((BLOCK - len(blk),) + blk.shape[1:], blk.dtype)
        y = _reference_block(params, jnp.asarray(np.concatenate([blk, pad])),
                             family, items, fp8)
        out.append(np.asarray(y)[:, :len(blk)])
    return np.concatenate(out, axis=1)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _alpha_block(images, d_items, fp8):
    if fp8:
        return gate.difficulty(fake_quant(images, keep=(0,)), dict(d_items),
                               jnp.bfloat16)
    return gate.difficulty(images, dict(d_items))


def reference_alpha(cfg, images, fp8=False):
    """(N,) reference Eq. 8 difficulty, ``BLOCK`` rows at a time; with
    ``fp8`` the control's: each image rounded to fp8, then bfloat16
    arithmetic."""
    items = tuple((k, tuple(v) if isinstance(v, list) else v)
                  for k, v in sorted(cfg["difficulty"].items()))
    out = []
    for a in range(0, len(images), BLOCK):
        blk = images[a:a + BLOCK]
        pad = np.zeros((BLOCK - len(blk),) + blk.shape[1:], blk.dtype)
        y = _alpha_block(jnp.asarray(np.concatenate([blk, pad])), items, fp8)
        out.append(np.asarray(y)[:len(blk)])
    return np.concatenate(out)


def decide(logits, alpha, tau, beta_diff):
    """What the reference gate serves from ``logits``: (exit, class,
    confidence) per row."""
    conf = np.asarray(gate.confidence(logits))
    exit_idx = gate.select_exit(conf, gate.thresholds(
        tau, np.ones_like(tau), alpha, beta_diff))
    rows = np.arange(logits.shape[1])
    return (exit_idx, np.argmax(logits[exit_idx, rows], -1),
            conf[exit_idx, rows])


def compare(served, logits, alpha, tau, beta_diff) -> dict:
    """The compared numbers for served (exit_idx, pred, conf, alpha) rows
    against reference ``logits`` (E, N, C) and ``alpha`` (N,)."""
    exit_idx = np.asarray(served["exit_idx"])
    rows = np.arange(len(exit_idx))
    at = logits[exit_idx, rows]                         # (N, C)
    conf = np.asarray(gate.confidence(logits))          # (E, N)
    eff = gate.thresholds(tau, np.ones_like(tau), alpha, beta_diff)
    e = logits.shape[0]
    viol = np.zeros(len(rows))
    for g in range(e - 1):
        early = exit_idx > g                 # the gate did not fire
        viol = np.where(early, np.maximum(viol, conf[g] - eff[:, g]), viol)
        here = exit_idx == g                 # the gate fired
        viol = np.where(here, np.maximum(viol, eff[:, g] - conf[g]), viol)
    conf_err = np.abs(np.asarray(served["conf"]) - conf[exit_idx, rows])
    return {
        "pred_gap": float(np.max(at.max(-1)
                                 - at[rows, np.asarray(served["pred"])])),
        "conf_err": float(np.max(conf_err)),
        "conf_err_mean": float(np.mean(conf_err)),
        "gate_violation": float(np.max(np.maximum(viol, 0.0))),
        "alpha_err": float(np.max(np.abs(np.asarray(served["alpha"])
                                         - alpha))),
    }


def served_rows(reqs) -> dict:
    return {k: np.concatenate([r.result[k] for r in reqs])
            for k in ("exit_idx", "pred", "conf", "alpha")}


def unanswered(reqs) -> int:
    """Requests whose answer never came or failed other than by refusal."""
    return sum(1 for r in reqs if r.result is None and not (
        r.error and any(k in r.error for k in REFUSALS)))
