"""A run whose timed path is broken underneath comes out not correct: an
answer altered where the masked step produces it."""
import io

import jax.numpy as jnp
import pytest

from bench.tests.tiny import make_root

from bench import run as R
from bench import spec


def _alter_class(exit_idx, conf, pred, n_exits):
    return exit_idx, conf, (pred + 5) % 10


def _alter_exit(exit_idx, conf, pred, n_exits):
    return jnp.minimum(exit_idx + 1, n_exits - 1), conf, pred


@pytest.mark.parametrize("fault", [_alter_class, _alter_exit],
                         ids=["class", "exit"])
def test_an_altered_answer_is_not_correct(tmp_path, monkeypatch, fault):
    from repro.engine.sharded import ShardedDartEngine
    route = ShardedDartEngine._route_traced

    def broken(self, logits, eff, min_exit=0):
        return fault(*route(self, logits, eff, min_exit=min_exit),
                     self.n_exits)

    monkeypatch.setattr(ShardedDartEngine, "_route_traced", broken)
    root = make_root(tmp_path, [("tiny.closed", "tiny_resnet",
                                 "tiny_closed")])
    log = io.StringIO()
    result = R.run_cell(spec.load_cell("tiny.closed", root), 77, 1.0, False,
                        require_tpu=False, out=log)
    assert result["correct"] is False, log.getvalue()
    key = "pred_gap" if fault is _alter_class else "gate_violation"
    c = result["compared"][key]
    assert c["value"] > c["limit"]
