"""End-to-end behaviour of the paper's system (paper §4 at test scale):

  train adapters on two synthetic tasks -> export packs -> rapid-switch a
  deployed model between them -> each pack recovers ITS task's loss ->
  naive multi-adapter fusion keeps both tasks better than the base model.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.configs import AdapterConfig, RunConfig, TrainConfig, get_smoke_config
from repro.configs.base import ShapeSpec
from repro.data import TaskSpec, batch_iterator, make_batch
from repro.models import lm
from repro.runtime import Trainer
from repro.runtime.trainer import TrainerConfig

SHAPE = ShapeSpec("tiny", 64, 8, "train")
ARCH = "starcoder2-7b"
STEPS = 60


@pytest.fixture(scope="module")
def adapters_and_base():
    run = RunConfig(model=get_smoke_config(ARCH), shape=SHAPE,
                    adapter=AdapterConfig(kind="shira", mask="wm",
                                          sparsity=0.9),
                    train=TrainConfig(learning_rate=2e-2, total_steps=STEPS,
                                      warmup_steps=3))
    packs, base = {}, None
    for task in (1, 2):
        t = Trainer(run, TrainerConfig())
        out = t.fit(STEPS, batches=batch_iterator(
            run.model, SHAPE, seed=0, task=TaskSpec(task_id=task)), log=None)
        packs[task] = t.export_pack(out["state"], name=f"task{task}")
        base = t.base
    return get_smoke_config(ARCH), base, packs


def eval_loss(cfg, params, task: int) -> float:
    batch = {k: jnp.asarray(v) for k, v in
             make_batch(cfg, SHAPE, seed=123, step=999,
                        task=TaskSpec(task_id=task)).items()}
    return float(lm.train_loss(params, cfg, batch)[0])


@pytest.mark.slow
def test_adapter_switching_recovers_each_task(adapters_and_base):
    cfg, base, packs = adapters_and_base
    eng = core.SwitchEngine(base)
    base_l1 = eval_loss(cfg, eng.params, 1)
    base_l2 = eval_loss(cfg, eng.params, 2)

    eng.switch(packs[1])
    l1 = eval_loss(cfg, eng.params, 1)
    assert l1 < base_l1 - 0.05, (l1, base_l1)

    eng.switch(packs[2])   # rapid switch: unload 1, load 2
    l2 = eval_loss(cfg, eng.params, 2)
    assert l2 < base_l2 - 0.05, (l2, base_l2)

    # after unloading everything the base model is recovered
    eng.unload()
    for a, b in zip(jax.tree.leaves(eng.params), jax.tree.leaves(base)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-5)


def test_multi_adapter_fusion_keeps_both_tasks(adapters_and_base):
    """Paper §4.3.2: naive fusion of sparse adapters keeps both concepts."""
    cfg, base, packs = adapters_and_base
    base_losses = {t: eval_loss(cfg, base, t) for t in (1, 2)}
    eng = core.SwitchEngine(base)
    eng.load_fused([packs[1], packs[2]])
    fused_losses = {t: eval_loss(cfg, eng.params, t) for t in (1, 2)}
    for t in (1, 2):
        assert fused_losses[t] < base_losses[t], (t, fused_losses, base_losses)


def test_pack_size_comparable_to_lora(adapters_and_base):
    """SHiRA packs are LoRA-sized on disk but patch only 1-2% of weights."""
    cfg, base, packs = adapters_and_base
    pack_bytes = packs[1].nbytes()
    model_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(base))
    assert pack_bytes < 0.35 * model_bytes


@pytest.mark.parametrize("from_env", [True, False])
def test_entry_point_compile_cache_dir(monkeypatch, tmp_path, from_env):
    """The entry points' compile cache follows JAX_COMPILATION_CACHE_DIR
    and sets nothing else; without it, a fixed directory in the checkout."""
    from pathlib import Path
    from repro.launch.compile_cache import enable_compile_cache
    prev = jax.config.jax_compilation_cache_dir
    repo = Path(__file__).resolve().parents[1]
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = enable_compile_cache()
        if from_env:
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == prev
        else:
            assert got == str(repo / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
