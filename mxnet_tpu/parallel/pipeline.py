"""Pipeline parallelism: microbatched GPipe stage loop, trainable.

The reference has no pipeline parallelism (SURVEY.md §2.3); provided as
a TPU-native capability. GPipe forward schedule expressed inside
``shard_map`` over the 'pp' mesh axis: each rank holds one stage's
params and an activation register; every tick it applies its stage and
passes the activation to the next rank via ``ppermute`` — XLA overlaps
the ICI hop with the next tick's compute.

The tick loop is a ``lax.scan``, so the whole schedule is REVERSE-MODE
DIFFERENTIABLE: ``jax.grad`` of a loss on the pipe's outputs yields the
GPipe backward schedule automatically (the scan transpose runs the ticks
in reverse and the ``ppermute`` transpose sends cotangents across the
inverse permutation — backward activations flow last-stage -> first).
``pipeline_value_and_grad`` packages that into a training step.

``pipeline_stage_loop`` constrains all stages to map activations of one
shape to the same shape. ``hetero_pipeline`` lifts that for real models
(ResNet/BERT stages change activation shapes): per-stage param pytrees
are raveled, zero-padded to the widest stage, and stacked into one
(n_stages, P_max) array sharded along 'pp' — each rank holds exactly its
own stage's weights. Activations travel between ranks as a padded
(mb, A_max) register; each rank applies a stage-indexed ``lax.switch``
whose branch statically unpacks its own input shape/params, runs its
sub-network, and repacks. Padding makes every ICI hop max-activation
sized — the SPMD price of shape-heterogeneous stages — but keeps the
whole schedule one jitted scan, still reverse-mode differentiable.
"""
from __future__ import annotations

import math as _math

import jax
import jax.flatten_util
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipeline_stage_loop", "pipeline_value_and_grad",
           "hetero_pipeline", "HeteroPipeline"]


def pipeline_stage_loop(stage_fn, n_microbatches: int, mesh: Mesh,
                        axis_name: str = "pp"):
    """Build ``f(stage_params, microbatches) -> outputs``.

    - ``stage_params``: pytree whose leaves carry a leading pp-sharded
      stage axis (leaf shape (n_stages, ...)); rank i uses slice i.
    - ``microbatches``: (n_microbatches, mb, ...) replicated input; rank 0
      feeds them into the pipe in order.
    - returns (n_microbatches, mb, ...) — the last stage's outputs,
      broadcast to all ranks.
    """
    n_stages = mesh.shape[axis_name]
    ticks = n_stages + n_microbatches - 1

    def local(params, mbs):
        # shard_map hands each rank its stage slice with leading dim 1
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        rank = lax.axis_index(axis_name)
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        reg0 = lax.pcast(jnp.zeros_like(mbs[0]), axis_name,
                         to="varying")
        out0 = lax.pcast(jnp.zeros_like(mbs), axis_name, to="varying")

        def tick(carry, t):
            reg, out = carry
            feed_idx = jnp.clip(t, 0, n_microbatches - 1)
            inp = jnp.where(rank == 0, mbs[feed_idx], reg)
            y = stage_fn(params, inp)
            # rank n-1 finishes microbatch t-(n_stages-1) at tick t
            done_idx = t - (n_stages - 1)
            valid = (done_idx >= 0) & (rank == n_stages - 1)
            slot = jnp.clip(done_idx, 0, n_microbatches - 1)
            out = out.at[slot].set(jnp.where(valid, y, out[slot]))
            reg = lax.ppermute(y, axis_name, perm)
            return (reg, out), None

        (reg, out), _ = lax.scan(tick, (reg0, out0),
                                 jnp.arange(ticks))
        # broadcast last rank's outputs to everyone
        out = jnp.where(rank == n_stages - 1, out, jnp.zeros_like(out))
        return lax.psum(out, axis_name)

    return shard_map(local, mesh=mesh,
                     in_specs=(P(axis_name), P()),
                     out_specs=P())


def pipeline_value_and_grad(stage_fn, loss_fn, n_microbatches: int,
                            mesh: Mesh, axis_name: str = "pp"):
    """Build a GPipe TRAINING step core:
    ``f(stage_params, microbatches, labels) -> (loss, grads)``.

    - ``loss_fn(outputs, labels) -> per-microbatch scalar`` is applied to
      each finished microbatch (labels shaped (n_microbatches, mb, ...));
      the reported loss is their mean.
    - ``grads`` has the same pp-sharded (n_stages, ...) structure as
      ``stage_params`` — each rank ends up holding exactly its own
      stage's gradients, computed by the reverse pipeline schedule that
      jax.grad derives from the forward scan.

    Wrap the result in ``jax.jit`` together with an optimizer update for
    a full pipeline-parallel train step (see tests/test_parallel.py and
    __graft_entry__.dryrun_multichip).
    """
    pipe = pipeline_stage_loop(stage_fn, n_microbatches, mesh,
                               axis_name=axis_name)

    def loss_of(params, mbs, labels):
        outs = pipe(params, mbs)
        per_mb = jax.vmap(loss_fn)(outs, labels)
        return per_mb.mean()

    def step(params, mbs, labels):
        return jax.value_and_grad(loss_of)(params, mbs, labels)

    return step


# --------------------------------------------------------------------------
# Heterogeneous stages (real models: activation shapes change per stage)
# --------------------------------------------------------------------------

class HeteroPipeline:
    """GPipe over stages with DIFFERENT param pytrees and activation
    shapes.

    Parameters
    ----------
    stage_fns : list of ``fn(params_pytree, x) -> y`` — stage i maps an
        activation of ``act_shapes[i]`` to ``act_shapes[i+1]`` (shapes
        exclude the microbatch dim).
    stage_params : list of per-stage param pytrees (used for layout
        metadata AND as the initial packed values).
    act_shapes : list of ``n_stages + 1`` activation shapes, microbatch
        dim excluded; ``act_shapes[0]`` is the pipe input,
        ``act_shapes[-1]`` the output.
    microbatch, n_microbatches, mesh, axis_name: schedule config.

    Attributes/methods
    ------------------
    ``packed``            initial (n_stages, P_max) param array — place it
                          with ``P(axis_name)`` sharding.
    ``unpack_params(a)``  packed array -> list of per-stage pytrees
                          (host-side inspection / checkpointing).
    ``pack_params(ps)``   inverse of ``unpack_params``.
    ``__call__(packed, mbs)`` forward: ``mbs`` is
                          (n_mb, microbatch) + act_shapes[0].
    ``value_and_grad(loss_fn)`` -> ``step(packed, mbs, labels) ->
                          (loss, packed_grads)`` where ``packed_grads``
                          matches ``packed`` (optimizer can update the
                          packed representation directly; unpack only to
                          inspect).
    """

    def __init__(self, stage_fns, stage_params, act_shapes, microbatch,
                 n_microbatches, mesh: Mesh, axis_name: str = "pp",
                 register_dtype=jnp.float32):
        n_stages = mesh.shape[axis_name]
        if len(stage_fns) != n_stages:
            raise ValueError(f"{len(stage_fns)} stage fns for a "
                             f"{n_stages}-way {axis_name!r} mesh axis")
        if len(act_shapes) != n_stages + 1:
            raise ValueError("need n_stages+1 activation shapes")
        self.mesh, self.axis_name = mesh, axis_name
        self.n_stages, self.n_microbatches = n_stages, n_microbatches
        self.microbatch = microbatch
        self.act_shapes = [tuple(s) for s in act_shapes]
        self._rdtype = register_dtype

        flat = [jax.flatten_util.ravel_pytree(p) for p in stage_params]
        self._sizes = [v.size for v, _ in flat]
        self._unravels = [u for _, u in flat]
        self._pmax = max(self._sizes)
        self.packed = jnp.stack([
            jnp.pad(v.astype(register_dtype), (0, self._pmax - v.size))
            for v, _ in flat])
        self._amax = max(_math.prod(s) if s else 1
                         for s in self.act_shapes)
        self._stage_fns = list(stage_fns)

    # ---- packing helpers -------------------------------------------------
    def pack_params(self, stage_params):
        vs = [jax.flatten_util.ravel_pytree(p)[0] for p in stage_params]
        return jnp.stack([
            jnp.pad(v.astype(self._rdtype), (0, self._pmax - v.size))
            for v in vs])

    def unpack_params(self, packed):
        return [self._unravels[i](packed[i, :self._sizes[i]])
                for i in range(self.n_stages)]

    def _pack_act(self, y):
        flat = y.reshape(y.shape[0], -1).astype(self._rdtype)
        return jnp.pad(flat, ((0, 0), (0, self._amax - flat.shape[1])))

    def _unpack_act(self, reg, stage):
        shape = self.act_shapes[stage]
        n = _math.prod(shape) if shape else 1
        return reg[:, :n].reshape((reg.shape[0],) + shape)

    def _branches(self):
        def make(i):
            def branch(pvec, reg):
                params = self._unravels[i](pvec[:self._sizes[i]])
                x = self._unpack_act(reg, i)
                y = self._stage_fns[i](params, x)
                return self._pack_act(y)
            return branch
        return [make(i) for i in range(self.n_stages)]

    # ---- schedule --------------------------------------------------------
    def __call__(self, packed, mbs):
        """Forward: (n_mb, microbatch) + act_shapes[0] -> outputs of
        shape (n_mb, microbatch) + act_shapes[-1], replicated."""
        n_stages, n_mb = self.n_stages, self.n_microbatches
        axis = self.axis_name
        ticks = n_stages + n_mb - 1
        branches = self._branches()

        def local(packed, mbs):
            pvec = packed[0]           # this rank's stage slice
            rank = lax.axis_index(axis)
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            mb_regs = jax.vmap(self._pack_act)(mbs)
            reg0 = lax.pcast(jnp.zeros_like(mb_regs[0]), axis,
                             to="varying")
            out0 = lax.pcast(jnp.zeros_like(mb_regs), axis,
                             to="varying")

            def tick(carry, t):
                reg, out = carry
                feed_idx = jnp.clip(t, 0, n_mb - 1)
                inp = jnp.where(rank == 0, mb_regs[feed_idx], reg)
                y = lax.switch(rank, branches, pvec, inp)
                done_idx = t - (n_stages - 1)
                # upper bound matters: with ticks > n_mb + n_stages - 1
                # the clip would let duplicate recomputations overwrite
                # the last slot (same values forward, but the backward
                # cotangent then rides the longer duplicate chain)
                valid = ((done_idx >= 0) & (done_idx <= n_mb - 1) &
                         (rank == n_stages - 1))
                slot = jnp.clip(done_idx, 0, n_mb - 1)
                out = out.at[slot].set(jnp.where(valid, y, out[slot]))
                reg = lax.ppermute(y, axis, perm)
                return (reg, out), None

            (_, out), _ = lax.scan(tick, (reg0, out0), jnp.arange(ticks))
            out = jnp.where(rank == n_stages - 1, out,
                            jnp.zeros_like(out))
            return lax.psum(out, axis)

        out = shard_map(local, mesh=self.mesh,
                        in_specs=(P(self.axis_name), P()),
                        out_specs=P())(packed, mbs)
        return jax.vmap(lambda r: self._unpack_act(r, self.n_stages))(out)

    def value_and_grad(self, loss_fn):
        """``step(packed, mbs, labels) -> (loss, packed_grads)`` — the
        reverse GPipe schedule falls out of differentiating the scan."""
        def loss_of(packed, mbs, labels):
            outs = self(packed, mbs)
            return jax.vmap(loss_fn)(outs, labels).mean()

        def step(packed, mbs, labels):
            return jax.value_and_grad(loss_of)(packed, mbs, labels)
        return step


def hetero_pipeline(stage_fns, stage_params, act_shapes, microbatch,
                    n_microbatches, mesh: Mesh, axis_name: str = "pp",
                    **kwargs):
    """Convenience constructor for :class:`HeteroPipeline`."""
    return HeteroPipeline(stage_fns, stage_params, act_shapes, microbatch,
                          n_microbatches, mesh, axis_name, **kwargs)
