"""Training-loop integration: losses decrease per family; microbatching
and compression paths train; BN stats update."""
import jax.numpy as jnp
import numpy as np

from repro.data.datasets import DatasetConfig
from repro.models.cnn_zoo import AlexNetConfig
from repro.models.dit import DiTConfig
from repro.models.resnet import ResNetConfig
from repro.models.transformer_lm import LMConfig
from repro.parallel.compression import CompressionConfig
from repro.runtime.trainer import Trainer, TrainConfig

DATA = DatasetConfig(name="synth-cifar", n_train=256, n_eval=64)


def losses(hist):
    return [h["loss"] for h in hist]


def test_cnn_loss_decreases():
    mc = AlexNetConfig(img_res=32, n_classes=10,
                       channels=(8, 16, 24, 16, 16), fc_dims=(64, 32))
    tr = Trainer(mc, TrainConfig(batch_size=16, steps=40, lr=3e-3,
                                 log_every=5), DATA)
    h = tr.run()
    assert min(losses(h)[1:]) < losses(h)[0]


def test_lm_loss_decreases():
    mc = LMConfig(name="t", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
                  d_ff=64, vocab=64, exit_layers=(0,), max_seq=32,
                  remat=False)
    tr = Trainer(mc, TrainConfig(batch_size=8, steps=30, lr=3e-3,
                                 log_every=5), DATA, data_kind="tokens")
    h = tr.run()
    assert losses(h)[-1] < losses(h)[0]


def test_dit_loss_decreases():
    mc = DiTConfig(name="d", img_res=64, patch=2, n_layers=2, d_model=32,
                   n_heads=2, n_classes=10, exit_layers=(0,), remat=False)
    tr = Trainer(mc, TrainConfig(batch_size=8, steps=30, lr=1e-3,
                                 log_every=5),
                 DatasetConfig(name="latents", img_res=64, n_train=128),
                 data_kind="latents")
    h = tr.run()
    assert losses(h)[-1] < losses(h)[0] * 1.05


def test_bn_running_stats_update():
    mc = ResNetConfig(name="r", depths=(1, 1), width=8, block="basic",
                      img_res=32, n_classes=10, small_input=True,
                      exit_stages=(0,))
    tr = Trainer(mc, TrainConfig(batch_size=16, steps=3, lr=1e-3), DATA)
    before = np.asarray(tr.params["stem"]["bn"]["mean"]).copy()
    tr.run()
    after = np.asarray(tr.params["stem"]["bn"]["mean"])
    assert not np.allclose(before, after)


def test_microbatching_matches_plain_step():
    """One microbatched step == one plain step, bit-for-bit (params)."""
    mc = AlexNetConfig(img_res=32, n_classes=10,
                       channels=(8, 16, 24, 16, 16), fc_dims=(64, 32))
    tc_a = TrainConfig(batch_size=16, steps=1, lr=3e-3, warmup=0)
    tc_b = TrainConfig(batch_size=16, steps=1, lr=3e-3, warmup=0,
                       microbatches=4)
    tr_a = Trainer(mc, tc_a, DATA)
    tr_b = Trainer(mc, tc_b, DATA)
    from repro.data.datasets import make_batch
    x, y = make_batch(DATA, range(16))
    tr_a.train_step((jnp.asarray(x), jnp.asarray(y)))
    tr_b.train_step((jnp.asarray(x), jnp.asarray(y)))
    import jax
    for a, b in zip(jax.tree.leaves(tr_a.params),
                    jax.tree.leaves(tr_b.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)


def test_microbatching_trains():
    mc = AlexNetConfig(img_res=32, n_classes=10,
                       channels=(8, 16, 24, 16, 16), fc_dims=(64, 32))
    # a short warmup: over the default 20 of 25 steps the learning rate
    # stays too small to move the loss off its initial value
    tr = Trainer(mc, TrainConfig(batch_size=16, steps=25, lr=3e-3, warmup=5,
                                 microbatches=4, log_every=5), DATA)
    h = tr.run()
    assert min(losses(h)[1:]) < losses(h)[0]


def test_compressed_training_matches_uncompressed_direction():
    mc = AlexNetConfig(img_res=32, n_classes=10,
                       channels=(8, 16, 24, 16, 16), fc_dims=(64, 32))
    tc = TrainConfig(batch_size=16, steps=40, lr=3e-3, log_every=5,
                     compression=CompressionConfig("int8"))
    tr = Trainer(mc, tc, DATA)
    h = tr.run()
    assert min(losses(h)[1:]) < losses(h)[0]
