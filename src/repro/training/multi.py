"""MultiAdapterTrainer: A sparse adapters finetuned in ONE jitted step.

The serving side already batches per-request adapters through the
``sidedelta`` side-term (one shared base matmul + per-request sparse
corrections, routed by a per-row adapter id). This trainer reuses exactly
that machinery for the *forward* pass of training, so A adapters'
finetuning batches share every base-weight matmul:

  * the packed trainables are batched as ``(A, …, K)`` value trees;
  * the step batch is the concatenation of each adapter's batch, with an
    ``ids`` row->adapter routing vector; weight leaves become
    ``sidedelta_weight`` bundles over the *trainable* value tables. They
    carry no ``sd.blocks``, so ``pdot`` runs them through the plain jnp
    side-delta (``kernels/ref.sidedelta_ref``: gather, scale, scatter-add),
    and autodiff gives the per-adapter scatter-add gradient reduction for
    free;
  * the loss is the SUM of per-adapter mean NLLs, so each adapter's value
    gradients are exactly what its own single-adapter run would produce
    (values only touch rows routed to them — no cross-terms);
  * gradients are clipped per adapter (``batched_global_norm``) and the
    fused ``kernels/sparse_adamw`` update runs over the batched packed
    axis (``sparse_adamw_rows``: one launch per leaf updates all A
    adapters), with optimizer moments optionally stored bf16/int8 between
    steps (``training.qstate``; dequant happens inside the kernel).

Equivalence contract (tested in tests/test_multiadapter.py): under f32
compute precision, adapter ``a`` of ``MultiAdapterTrainer(run, names,
init_key=k)`` fed task ``TaskSpec(a)`` tracks ``Trainer(run, init_key=k +
a)`` fed the same stream, step for step, within float-summation-order
tolerance. MoE archs add the load-balance aux over the *combined* batch
(a documented cross-term); the parity contract is for dense archs.

Closing the loop into serving: ``export_packs`` emits one ``AdapterPack``
per adapter; ``publish`` pushes them through ``AdapterStore.publish`` as
versioned ids (``name@v``) and optionally snapshots them via
``CheckpointManager.save_adapter``, under ``publish.swap`` trace spans.
Live engines pick up the new version for new submissions while in-flight
requests stay pinned to the old one (see hub/serving.py).
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro import core
from repro.analysis import trace
from repro.configs.base import RunConfig
from repro.data import TaskSpec, make_batch
from repro.kernels import ops
from repro.models import lm
from repro.models.layers import rms_norm, sidedelta_weight, unembed
from repro.optim import batched_global_norm, lr_schedule
from repro.runtime.trainer import TrainerConfig
from repro.training import qstate


def multi_batch_iterator(cfg, shape, seed: int, tasks: Sequence[TaskSpec],
                         start_step: int = 0) -> Iterator[Dict[str, Any]]:
    """Concatenation of ``len(tasks)`` per-adapter streams + row->adapter
    ids. Row block ``a`` of every batch is bit-identical to what
    ``batch_iterator(cfg, shape, seed, task=tasks[a])`` yields at the same
    step — the sequential-equivalence tests rely on this."""
    import numpy as np
    A = len(tasks)
    n = shape.global_batch
    ids = np.repeat(np.arange(A, dtype=np.int32), n)
    step = start_step
    while True:
        parts = [make_batch(cfg, shape, seed, step, t) for t in tasks]
        batch = {k: np.concatenate([p[k] for p in parts], axis=0)
                 for k in parts[0]}
        batch["ids"] = ids
        yield batch
        step += 1


def _tuple_part(flat, i):
    return [None if t is None else t[i] for t in flat]


class MultiAdapterTrainer:
    """Concurrent packed-SHiRA finetuning of ``len(names)`` adapters.

    Args:
      run: shared RunConfig (``run.adapter`` must be packed SHiRA).
      names: adapter names, one per concurrent finetune; adapter ``a``
        inits from ``PRNGKey(init_key + a)`` — the same key its
        single-adapter ``Trainer(run, init_key=init_key + a)`` twin uses.
      moments: optimizer-moment storage, ``"f32"`` (default / oracle),
        ``"bf16"``, or ``"int8"`` (see ``training.qstate``).
      fused: route the update through the batched Pallas kernel
        (``sparse_adamw_rows``); False runs the pure-jnp reference with
        identical math — the kernel's parity oracle.
      interpret: Pallas interpret mode for the update kernel
        (None = auto: interpret off-TPU).
    """

    def __init__(self, run: RunConfig, names: Sequence[str],
                 tcfg: TrainerConfig = TrainerConfig(), *,
                 init_key: int = 0, base_params=None,
                 moments: str = "f32", fused: bool = True,
                 interpret: Optional[bool] = None):
        if run.adapter.kind != "shira" or not run.adapter.packed:
            raise ValueError("MultiAdapterTrainer is packed-SHiRA only; "
                             f"got kind={run.adapter.kind!r} "
                             f"packed={run.adapter.packed}")
        if moments not in qstate.MOMENT_MODES:
            raise ValueError(f"moments={moments!r} not in "
                             f"{qstate.MOMENT_MODES}")
        self.run, self.tcfg = run, tcfg
        self.cfg, self.acfg = run.model, run.adapter
        self.names = list(names)
        self.A = len(self.names)
        self.moments = moments
        self.fused = fused
        self.interpret = (jax.default_backend() != "tpu"
                          if interpret is None else interpret)
        self.base = (base_params if base_params is not None
                     else lm.init_params(self.cfg, jax.random.PRNGKey(init_key)))
        # Per-adapter init with each twin's exact key: K per leaf depends
        # only on the weight shape, so the A index trees stack cleanly.
        inits = [core.init_adapter(jax.random.PRNGKey(init_key + a),
                                   self.base, self.acfg)
                 for a in range(self.A)]
        self.auxes = [aux for _, aux in inits]
        none_leaf = lambda x: x is None
        self.idx = jax.tree.map(
            lambda *xs: None if xs[0] is None else jnp.stack(xs),
            *[aux["indices"] for aux in self.auxes], is_leaf=none_leaf)
        # Device coordinate tables, built once: (lead…, A, K) so lax.scan
        # over stacked layer weights slices them exactly like the weights.
        def coords(w, i):
            if i is None:
                return None
            return jnp.moveaxis(i, 0, -2) % jnp.int32(w.shape[-1])
        def coords_r(w, i):
            if i is None:
                return None
            return jnp.moveaxis(i, 0, -2) // jnp.int32(w.shape[-1])
        self.rows = jax.tree.map(coords_r, self.base, self.idx,
                                 is_leaf=none_leaf)
        self.cols = jax.tree.map(coords, self.base, self.idx,
                                 is_leaf=none_leaf)
        self.values0 = jax.tree.map(
            lambda i: None if i is None else jnp.zeros(i.shape, jnp.float32),
            self.idx, is_leaf=none_leaf)
        self._ids = jnp.repeat(jnp.arange(self.A, dtype=jnp.int32),
                               run.shape.global_batch)
        self.schedule = lr_schedule(run.train)
        self._step_fn = None

    # -- state ---------------------------------------------------------------

    def init_state(self) -> Dict[str, Any]:
        none_leaf = lambda x: x is None
        # fresh zeros per moment tree: sharing one buffer between mu and nu
        # would break the jitted step's donation (same buffer donated twice)
        enc = lambda sqrt_dom: jax.tree.map(
            lambda v: None if v is None
            else qstate.encode(jnp.zeros_like(v, jnp.float32), self.moments,
                               sqrt_dom),
            self.values0, is_leaf=none_leaf)
        mu, nu = enc(False), enc(True)
        tup = lambda x: isinstance(x, tuple)
        pick = lambda t, i: jax.tree.map(lambda p: p[i], t, is_leaf=tup)
        values = jax.tree.map(          # fresh too: the step donates state
            lambda v: None if v is None else jnp.zeros_like(v),
            self.values0, is_leaf=none_leaf)
        return {"values": values,
                "mu": pick(mu, 0), "nu": pick(nu, 0),
                "mu_scale": pick(mu, 1), "nu_scale": pick(nu, 1),
                "step": jnp.zeros((), jnp.int32)}

    # -- forward -------------------------------------------------------------

    def _wrapped_params(self, values):
        """Base tree with target leaves replaced by sidedelta bundles over
        the TRAINABLE (A, …, K) value tables — gradients flow through the
        bundle's ``sd.vals`` entry via the plain jnp side-delta (the
        bundles carry no ``sd.blocks``)."""
        none_leaf = lambda x: x is None

        def leaf(w, r, c, v):
            if r is None:
                return w
            lead = w.shape[:-2]
            return sidedelta_weight(
                w, r, c, jnp.moveaxis(v, 0, -2),
                jnp.broadcast_to(self._ids, lead + self._ids.shape))

        return jax.tree.map(leaf, self.base, self.rows, self.cols, values,
                            is_leaf=none_leaf)

    def _per_adapter_loss(self, params, batch):
        """(A,) mean NLL per adapter + aux — ``lm.chunked_loss`` math with
        the scalar accumulator widened to a one-hot-routed (A,) vector, so
        every adapter's loss normalizes over ITS rows only (what its own
        single-adapter run would compute)."""
        cfg, A = self.cfg, self.A
        if cfg.modality != "text":
            raise NotImplementedError("multi-adapter training routes by "
                                      "token rows; text modality only")
        h, prefix_len = lm.embed_inputs(params, cfg, batch)
        aux = jnp.zeros((), jnp.float32)
        for sp, (kind, _) in zip(params["stages"], lm.stage_plan(cfg)):
            h, aux = lm._stage_train(sp, kind, cfg, h, aux, prefix_len,
                                     shared=params.get("shared_attn"))
        h = rms_norm(h, params["final_norm"]["scale"], cfg.norm_eps)
        B, S, d = h.shape
        T = B * S
        hf = h.reshape(T, d)
        lf = batch["labels"].reshape(T)
        af = jnp.repeat(batch["ids"].astype(jnp.int32), S)
        tie = params["embed"]["emb"] if cfg.tie_embeddings else None
        un = params.get("unembed")
        c = lm._pick_chunk(T)
        n = T // c

        def body(carry, xs):
            hc, lc, ac = xs
            from repro.launch.actctx import shard_as
            hc = shard_as(hc, "loss_act")
            logits = unembed(un, hc, tie_to=tie, softcap=cfg.logit_softcap,
                             logical_vocab=cfg.vocab_size)
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
            nll = logz - gold                                   # (c,)
            onehot = (ac[:, None] == jnp.arange(A)[None, :]).astype(
                jnp.float32)                                    # (c, A)
            sums, counts = carry
            return (sums + nll @ onehot,
                    counts + jnp.sum(onehot, axis=0)), None

        body = jax.checkpoint(body)
        (sums, counts), _ = jax.lax.scan(
            body, (jnp.zeros((A,), jnp.float32), jnp.zeros((A,), jnp.float32)),
            (hf.reshape(n, c, d), lf.reshape(n, c), af.reshape(n, c)))
        return sums / jnp.maximum(counts, 1.0), aux

    # -- the pure step -------------------------------------------------------

    def _update_leaf(self, v, g, m, u, ms, us, step, lr):
        tc = self.run.train
        K = v.shape[-1]
        R = v.size // K
        shp = v.shape
        row = lambda x: None if x is None else x.reshape(R, K)
        sc = lambda x: None if x is None else x.reshape(R)
        if self.fused:
            v2, m2, u2 = ops.sparse_adamw_batched(
                row(v), row(g), row(m), row(u), step, lr=lr,
                b1=tc.beta1, b2=tc.beta2, eps=tc.eps, wd=tc.weight_decay,
                mu_scale=sc(ms), nu_scale=sc(us), interpret=self.interpret)
            v2, m2, u2 = v2.reshape(shp), m2.reshape(shp), u2.reshape(shp)
        else:   # pure-jnp reference: identical math, the kernel's oracle
            mf = qstate.decode(m, ms, self.moments)
            uf = qstate.decode(u, us, self.moments, sqrt_domain=True)
            gf = g.astype(jnp.float32)
            stepf = step.astype(jnp.float32)
            m2 = tc.beta1 * mf + (1.0 - tc.beta1) * gf
            u2 = tc.beta2 * uf + (1.0 - tc.beta2) * gf * gf
            mh = m2 / (1.0 - tc.beta1 ** stepf)
            uh = u2 / (1.0 - tc.beta2 ** stepf)
            delta = mh / (jnp.sqrt(uh) + tc.eps) + tc.weight_decay * v
            v2 = v - lr * delta
        m_st, ms2 = qstate.encode(m2, self.moments)
        u_st, us2 = qstate.encode(u2, self.moments, sqrt_domain=True)
        return v2, m_st, u_st, ms2, us2

    def build_step(self) -> Callable:
        tc = self.run.train
        none_leaf = lambda x: x is None

        def step_fn(state, batch):
            lr = self.schedule(state["step"])

            def loss_fn(values):
                losses, aux = self._per_adapter_loss(
                    self._wrapped_params(values), batch)
                loss = jnp.sum(losses)
                if self.cfg.family == "moe" or (
                        self.cfg.moe and self.cfg.moe.num_experts):
                    loss = loss + 0.01 * aux
                return loss, {"losses": losses, "aux": aux}

            (_, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state["values"])
            gnorm = batched_global_norm(grads, self.A)           # (A,)
            if tc.grad_clip > 0:
                scale = jnp.minimum(1.0, tc.grad_clip / (gnorm + 1e-9))
                grads = jax.tree.map(
                    lambda g: g * scale.reshape((self.A,) + (1,) * (g.ndim - 1)),
                    grads)
            step = state["step"] + 1
            flat = lambda t: jax.tree_util.tree_flatten(t, is_leaf=none_leaf)
            fv, treedef = flat(state["values"])
            fg = flat(grads)[0]
            fm, fu = flat(state["mu"])[0], flat(state["nu"])[0]
            fms, fus = flat(state["mu_scale"])[0], flat(state["nu_scale"])[0]
            out = [(None,) * 5 if v is None
                   else self._update_leaf(v, g, m, u, ms, us, step, lr)
                   for v, g, m, u, ms, us in zip(fv, fg, fm, fu, fms, fus)]
            unf = lambda i: jax.tree_util.tree_unflatten(
                treedef, _tuple_part(out, i))
            new_state = {"values": unf(0), "mu": unf(1), "nu": unf(2),
                         "mu_scale": unf(3), "nu_scale": unf(4), "step": step}
            metrics = {**metrics, "loss": jnp.mean(metrics["losses"]),
                       "grad_norm": gnorm, "lr": lr}
            return new_state, metrics

        return jax.jit(step_fn, donate_argnums=(0,))

    # -- host loop -----------------------------------------------------------

    def fit(self, steps: int, batches: Optional[Iterator] = None,
            state: Optional[dict] = None,
            log: Optional[Callable[[str], None]] = print) -> Dict[str, Any]:
        if self._step_fn is None:
            self._step_fn = self.build_step()
        if batches is None:
            batches = multi_batch_iterator(
                self.cfg, self.run.shape, self.run.train.seed,
                [TaskSpec(a) for a in range(self.A)])
        state = state or self.init_state()
        it = iter(batches)
        history = []
        for s in range(steps):
            batch = {k: jnp.asarray(v) for k, v in next(it).items()}
            t0 = time.perf_counter()
            with trace.span("train.step", cat="train", step=s,
                            adapters=self.A):
                state, metrics = self._step_fn(state, batch)
                jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            losses = [float(x) for x in metrics["losses"]]
            rec = {"loss": float(metrics["loss"]), "lr": float(metrics["lr"]),
                   "step_ms": dt * 1e3}
            rec.update({f"loss:{n}": v for n, v in zip(self.names, losses)})
            history.append(rec)
            if log and (s % self.tcfg.log_every == 0 or s == steps - 1):
                per = " ".join(f"{n}={v:.4f}"
                               for n, v in zip(self.names, losses))
                log(f"[multi] step {s:5d} {per} {dt*1e3:.0f}ms")
        return {"state": state, "history": history}

    # -- export / publish ----------------------------------------------------

    def export_packs(self, state) -> List[core.AdapterPack]:
        none_leaf = lambda x: x is None
        packs = []
        for a, name in enumerate(self.names):
            vals = jax.tree.map(lambda v: None if v is None else v[a],
                                state["values"], is_leaf=none_leaf)
            packs.append(core.pack_from_shira(name, vals, self.auxes[a]))
        return packs

    def publish(self, store, state, *, ckpt=None, step: Optional[int] = None,
                values: str = "f32") -> List[str]:
        """Push every adapter's current values into the store as a fresh
        version (``name@v``); optionally snapshot the versioned packs into
        a checkpoint step (committed by the next ``ckpt.save``). Live
        engines hot-swap on their next submit — see hub/serving.py."""
        step = int(state["step"]) if step is None else step
        vids = []
        for pack in self.export_packs(state):
            with trace.span("publish.swap", cat="train", name=pack.name):
                vid = store.publish(pack, values=values)
                if ckpt is not None:
                    ckpt.save_adapter(step, core.AdapterPack(
                        vid, pack.entries, pack.alpha), values=values)
            vids.append(vid)
        return vids
