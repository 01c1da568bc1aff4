"""DART on a language model: train a small multi-exit LM, then decode with
REAL per-token layer skipping + CALM state propagation (DESIGN.md §3),
through the queue-backed session handle over the ``repro.engine`` LM
decode engine: concurrent callers submit prompts with deadlines and the
scheduler consolidates them into shared bucketed decode loops — each
consolidated bucket running the SHARDED jit-end-to-end decode step (one
donated-cache compiled program per (stage, bucket); the eager per-stage
oracle is one ``mode="eager"`` away).

Run:  PYTHONPATH=src python examples/lm_early_exit.py
"""
import numpy as np
import jax.numpy as jnp

from repro.core.routing import DartParams
from repro.data.datasets import DatasetConfig, make_batch
from repro.engine import LMDecodeEngine
from repro.launch.mesh import make_serving_mesh
from repro.models.transformer_lm import LMConfig
from repro.runtime.trainer import Trainer, TrainConfig

DATA = DatasetConfig(name="tokens", n_train=2048)
CFG = LMConfig(name="lm-demo", n_layers=6, d_model=64, n_heads=4,
               n_kv_heads=2, d_ff=128, vocab=64, exit_layers=(1, 3),
               max_seq=64, remat=False)


def main():
    print("training 6-layer LM with exits at layers 1 and 3 ...")
    tr = Trainer(CFG, TrainConfig(batch_size=16, steps=400, lr=5e-3,
                                  log_every=30), DATA, data_kind="tokens")
    tr.run()
    print("loss:", [round(h["loss"], 3) for h in tr.history])

    dart = DartParams(tau=jnp.asarray([0.35, 0.4]), coef=jnp.ones(2),
                      beta_diff=0.15)
    srv = LMDecodeEngine(CFG, tr.params, dart, mesh=make_serving_mesh())

    prompts, _ = make_batch(DATA, range(8), kind="tokens", seq_len=17,
                            vocab=CFG.vocab)
    # sanity: the fused compiled decode loop is bit-identical to the
    # eager per-stage oracle (tokens AND exit depths)
    chk_s = srv.generate(prompts[:2, :9], n_new=4)
    chk_e = srv.generate(prompts[:2, :9], n_new=4, mode="eager")
    assert all(np.array_equal(a, b) for a, b in zip(chk_s, chk_e))
    # Queue-backed session: 8 concurrent "callers" each submit one
    # prompt; the scheduler lanes them by (prompt_len, n_new) and all
    # eight share ONE bucketed early-exit decode loop.
    session = srv.session()
    futs = [session.submit(prompts[i, :9], n_new=16) for i in range(8)]
    outs = [f.result() for f in futs]
    session.close()
    gen = np.concatenate([o["tokens"] for o in outs])
    stages = np.concatenate([o["stages"] for o in outs])
    sstats = session.stats()
    print(f"\nsession: {sstats['scheduler']['submitted']} callers -> "
          f"{sstats['scheduler']['flush_deadline'] + sstats['scheduler']['flush_size'] + sstats['scheduler']['flush_forced'] + sstats['scheduler']['flush_hold']} "
          f"consolidated decode call(s); p95 latency "
          f"{sstats['requests']['latency_ms']['p95']:.0f} ms")
    print("generated shapes:", gen.shape)
    print("exit-stage histogram over generated tokens:",
          np.bincount(stages.ravel(), minlength=3).tolist(),
          "(stage 0 = after layer 1, 1 = after layer 3, 2 = full depth)")
    total = srv.layers_run + srv.layers_skipped
    print(f"layers run {srv.layers_run}, skipped {srv.layers_skipped} "
          f"({100*srv.layers_skipped/max(total,1):.1f}% of full-depth "
          f"compute avoided; skipped layers only pay the KV-projection "
          f"propagation)")

    # token continuation quality check: motif should be continued
    print("\nprompt   :", prompts[0, :9].tolist())
    print("generated:", gen[0].tolist())


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
