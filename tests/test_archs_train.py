"""Per-architecture smoke test of one train step on CPU: reduced config,
finite loss, parameters updated (deliverable f).  Kept apart from
test_archs.py so that the two halves run on different xdist workers."""

import jax
import numpy as np
import pytest

from _archs import ARCHS, arch_state, make_batch  # noqa: F401 (fixture)
from repro.train.optimizer import OptimizerConfig, init_opt_state
from repro.train.train_step import TrainConfig, make_train_step


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_no_nans(arch, arch_state):
    cfg, params = arch_state(arch)
    key = jax.random.key(2)
    batch = make_batch(cfg, key)
    ocfg = OptimizerConfig()
    step = make_train_step(cfg, None, ocfg, TrainConfig(remat=False))
    opt = init_opt_state(params, ocfg)
    p2, o2, metrics = jax.jit(step)(params, opt, batch)
    loss = float(metrics["loss"])
    assert np.isfinite(loss) and loss > 0
    # params actually changed (some leaves move by only ~lr*1e-2; exact
    # inequality on any leaf is the right check)
    changed = any(
        not np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(p2)))
    assert changed
