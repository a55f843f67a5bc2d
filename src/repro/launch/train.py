"""Distributed training step builder + driver.

``build_train_step(cfg, mesh, ...)`` returns a jitted SPMD step:
  params/opt-state fully sharded (parallel.sharding greedy FSDP×TP×EP),
  batch over the DP axes, per-layer remat under the layer scan,
  optional int8-EF gradient compression and 8-bit Adam moments.

The driver (main) wires data pipeline → step → checkpointing → fault
tolerance and runs a real (small) training job on the local device — the
same code lowers to the 512-chip production mesh in launch.dryrun.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.core.backend import JOps
from repro.data import pipeline
from repro.models import transformer as T
from repro.optim import optimizer as opt
from repro.optim import grad_compress as gc
from repro.parallel import sharding as sh
from repro.launch import mesh as meshlib


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    arch: str = "qwen2_7b"
    smoke: bool = True
    seq: int = 128
    global_batch: int = 8
    steps: int = 50
    compute_dtype: str = "float32"     # bf16 on TPU
    remat: bool = True
    quantized_moments: bool = False
    grad_compression: bool = False
    # "fsdp": greedy ZeRO-3 sharding of params over model+data (needed for
    # 400B-class and MoE); "tp": params model-axis-resident (≤35B dense —
    # avoids data-axis parameter gathers and SPMD resharding churn);
    # "auto": per-arch policy matrix from §Perf
    param_sharding: str = "auto"
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 20
    seed: int = 0


class RematJOps(JOps):
    """JOps whose layer loop checkpoints each layer (full remat).

    The rematerialised residual carry is constrained to be model-axis
    sharded on its feature dim (Megatron sequence-parallel style): the
    per-layer saved activation shrinks 16× — without this, 40-plus-layer
    train cells blow HBM on saved residuals alone (§Perf)."""

    def _residual_constraint(self, x):
        mesh = self.mesh
        if mesh is None or x.ndim != 3:
            return x
        from jax.sharding import NamedSharding, PartitionSpec as P
        dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        d = x.shape[-1]
        m = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
        if m > 1 and d % m == 0:
            spec = P(dp or None, None, "model")
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, spec))
        return x

    def shard_hint(self, a, kind: str):
        """Sequence-parallel attention: shard the query sequence over the
        'model' axis so the [B,H,S,S] score tensor shards 16× even when the
        KV-head count doesn't divide the axis (kv=8 archs replicate it
        otherwise — the dominant train-cell temp, §Perf)."""
        mesh = self.mesh
        if mesh is None or kind != "q_seq" or a.ndim < 3:
            return a
        from jax.sharding import NamedSharding, PartitionSpec as P
        m = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
        S = a.shape[1]
        if m > 1 and S % m == 0:
            dp = tuple(ax for ax in ("pod", "data") if ax in mesh.axis_names)
            spec = P(dp or None, "model", *([None] * (a.ndim - 2)))
            return jax.lax.with_sharding_constraint(
                a, NamedSharding(mesh, spec))
        return a

    def layer_loop(self, fn, stacked_params, x, n_layers: int, aux=None,
                   first: int = 0):
        def fn_constrained(p, h, i, a):
            h, a = fn(p, h, i, a)
            return self._residual_constraint(h), a

        fn_r = jax.checkpoint(fn_constrained, static_argnums=())

        def body(carry, xs):
            (h, a), (p, i) = carry, xs
            return fn_r(p, h, i + first, a), None
        idx = jnp.arange(n_layers)
        x = self._residual_constraint(x)
        (out, aux), _ = jax.lax.scan(body, (x, aux), (stacked_params, idx))
        return out, aux


def _backend(tc: TrainConfig, remat: Optional[bool] = None, mesh=None):
    dt = jnp.bfloat16 if tc.compute_dtype == "bfloat16" else jnp.float32
    cls = RematJOps if (tc.remat if remat is None else remat) else JOps
    return cls(dt, jnp.float32, mesh=mesh)


def make_loss_fn(arch_cfg, tc: TrainConfig, frontend_shapes=None, mesh=None):
    bk = _backend(tc, mesh=mesh)

    def loss_fn(params, batch):
        kwargs = {}
        if arch_cfg.frontend == "audio":
            kwargs["enc_embeds"] = batch["frontend"]
        elif arch_cfg.frontend == "vision":
            kwargs["frontend_embeds"] = batch["frontend"]
        return T.next_token_loss(bk, params, arch_cfg, batch["tokens"],
                                 batch["targets"], **kwargs)

    return loss_fn


def build_train_step(arch_cfg, tc: TrainConfig, mesh, adam_cfg=None):
    """Returns (step_fn, init_fn, shardings dict). step_fn is jitted with
    explicit in/out shardings — the same object the dry-run lowers."""
    adam_cfg = adam_cfg or opt.AdamWConfig(
        quantized_moments=tc.quantized_moments, total_steps=tc.steps)
    loss_fn = make_loss_fn(arch_cfg, tc, mesh=mesh)

    def init_fn(key):
        params = T.init_params(key, arch_cfg)
        state = opt.init(params, adam_cfg)
        ef = gc.init_ef(params) if tc.grad_compression else None
        return {"params": params, "opt": state, "ef": ef}

    def step_fn(train_state, batch):
        params = train_state["params"]
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        if tc.grad_compression:
            grads, new_ef = gc.compress_tree(grads, train_state["ef"])
        else:
            new_ef = None
        new_params, new_opt = opt.update(params, grads, train_state["opt"],
                                         adam_cfg)
        return {"params": new_params, "opt": new_opt, "ef": new_ef}, loss

    # shardings
    key = jax.random.PRNGKey(tc.seed)
    pshapes = jax.eval_shape(lambda: T.init_params(key, arch_cfg))
    mode = tc.param_sharding
    if mode == "auto":  # §Perf policy matrix
        dense_small = (arch_cfg.family != "moe"
                       and T.analytic_params(arch_cfg) <= 40e9)
        mode = "tp" if dense_small else "fsdp"
    p_sh = sh.shard_params(pshapes, mesh, model_only=(mode == "tp"))

    def state_shardings():
        opt_shapes = jax.eval_shape(
            lambda: opt.init(jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), pshapes), adam_cfg))
        o_sh = _opt_shardings(opt_shapes, p_sh, mesh)
        ef_sh = p_sh if tc.grad_compression else None
        return {"params": p_sh, "opt": o_sh, "ef": ef_sh}

    st_sh = state_shardings()
    b_sh = {
        "tokens": sh.shard_batch(mesh, tc.global_batch, tc.seq),
        "targets": sh.shard_batch(mesh, tc.global_batch, tc.seq),
    }
    if arch_cfg.frontend:
        b_sh["frontend"] = NamedSharding(
            mesh, sh.batch_spec(mesh, tc.global_batch, arch_cfg.frontend_seq))

    jitted = jax.jit(step_fn,
                     in_shardings=(st_sh, b_sh),
                     out_shardings=(st_sh, NamedSharding(mesh, P())),
                     donate_argnums=(0,))
    return jitted, init_fn, {"state": st_sh, "batch": b_sh}


def _opt_shardings(opt_shapes, p_sh, mesh):
    """Moments inherit the param shardings when shapes match (ZeRO);
    quantised payloads/scales ([blocks, block]-shaped) get the same greedy
    fully-sharded rule as parameters; scalars replicate."""
    rep = NamedSharding(mesh, P())

    def for_tree(ms, like_params: bool):
        def one(path, m_leaf):
            if like_params:
                ref = p_sh
                for p in path:
                    key = getattr(p, "key", getattr(p, "idx", None))
                    ref = ref[key] if isinstance(ref, (dict, list)) else ref
                if isinstance(ref, NamedSharding) and len(ref.spec) == len(m_leaf.shape):
                    return ref
            spec = sh._greedy_param_spec(m_leaf.shape, mesh, stacked=False)
            return NamedSharding(mesh, spec)
        return jax.tree_util.tree_map_with_path(one, ms)

    quant = opt_shapes.m_scale is not None
    return opt.OptState(
        step=rep,
        m=for_tree(opt_shapes.m, like_params=not quant),
        v=for_tree(opt_shapes.v, like_params=not quant),
        m_scale=None if not quant else for_tree(opt_shapes.m_scale, False),
        v_scale=None if not quant else for_tree(opt_shapes.v_scale, False),
    )


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_7b")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=4)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--quantized-moments", action="store_true")
    args = ap.parse_args(argv)

    arch_cfg = configs.get(args.arch).SMOKE
    tc = TrainConfig(arch=args.arch, seq=args.seq,
                     global_batch=args.global_batch, steps=args.steps,
                     grad_compression=args.grad_compression,
                     quantized_moments=args.quantized_moments,
                     checkpoint_dir=args.checkpoint_dir)
    mesh = meshlib.make_host_mesh()
    dc = pipeline.DataConfig(vocab=arch_cfg.vocab, seq=tc.seq,
                             global_batch=tc.global_batch)

    with mesh:
        step_fn, init_fn, _ = build_train_step(arch_cfg, tc, mesh)
        state = init_fn(jax.random.PRNGKey(tc.seed))
        ck = None
        if tc.checkpoint_dir:
            from repro.checkpoint.checkpointing import Checkpointer
            ck = Checkpointer(tc.checkpoint_dir)
        t0 = time.perf_counter()
        for step in range(tc.steps):
            batch = pipeline.batch_at(dc, step)
            if arch_cfg.frontend:
                import numpy as np
                rng = np.random.RandomState(step)
                batch["frontend"] = rng.randn(
                    tc.global_batch, arch_cfg.frontend_seq,
                    arch_cfg.frontend_dim).astype("float32")
            state, loss = step_fn(state, batch)
            if step % 10 == 0 or step == tc.steps - 1:
                print(f"step {step:5d}  loss {float(loss):.4f}  "
                      f"({time.perf_counter()-t0:.1f}s)")
            if ck and step and step % tc.checkpoint_every == 0:
                ck.save(step, state, blocking=False)
        if ck:
            ck.wait()


if __name__ == "__main__":
    main()
