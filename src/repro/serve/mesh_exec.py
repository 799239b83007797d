"""``MeshExec`` — the ServeEngine execution substrate over a
("data", "model") mesh: DP replicas of a tensor-parallel serving cell.

The pool rides with leading (dp, tp) axes so shard_map hands each PE
its own (rank-varying) page shard; host-visible tokens come back
stacked per replica and the host reads its own cell's row.  Page
migration between replicas is ``put_nbi`` rounds over the flattened
("data", "model") team drained by ONE ``quiet()`` through the real
``PermuteTransport``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.ordering import CommQueue, PermuteTransport
from repro.parallel.ctx import smap

from .engine import make_decode_step, make_prefill, make_verify

POOL_SPEC = P("data", "model")


def init_sharded_params(api, cfg, ctx, mesh, key):
    """``api.init`` evaluated straight into the ``api.specs`` layout on
    ``mesh``: each device materializes only its own shards, so a model
    larger than one device's memory never lands whole on device 0."""
    ctx1 = ctx.with_(dp_size=1, tp_size=1)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             api.specs(cfg, ctx),
                             is_leaf=lambda x: isinstance(x, P))
    return jax.jit(lambda k: api.init(k, cfg, ctx1),
                   out_shardings=shardings)(key)


class MeshExec:
    """Step functions shard_mapped over ``mesh`` (axes "data", "model";
    ``ctx`` sized to match), params laid out by ``pspecs``."""

    def __init__(self, params, pspecs, cfg, ctx, scfg, kv, mesh, my_pe=0):
        self.params, self.kv, self.mesh = params, kv, mesh
        self.dp, self.tp = mesh.devices.shape
        self.my_pe = int(my_pe)       # which replica this cell reads
        pf = make_prefill(cfg, ctx, scfg)
        dc = make_decode_step(cfg, ctx, scfg)
        vf = make_verify(cfg, ctx, scfg)

        # tokens are replica-varying once pages migrate (replica 1 may
        # hold pages replica 0 does not), so they come back stacked per
        # replica — the host reads its own cell's row
        def wrap(step):
            def body(params, pool, *args):
                toks, kvo = step(params, pool[0, 0], *args)
                return toks, kvo[None, None]
            return jax.jit(smap(
                body, mesh, (pspecs, POOL_SPEC, P(), P(), P(), P(), P()),
                (P("data"), POOL_SPEC)))

        self._prefill = wrap(pf)
        self._decode = wrap(dc)
        self._verify = wrap(vf)
        self._migrate_cache = {}

    def _my_row(self, toks):
        # (DP*b,) token vectors and (DP*b, C) verify windows alike
        t = np.asarray(toks)
        return t.reshape((self.dp, -1) + t.shape[1:])[self.my_pe]

    def init_pool(self):
        shape = (self.dp, self.tp) + self.kv.handle.shape
        return jax.jit(lambda: jnp.zeros(shape, self.kv.handle.dtype),
                       out_shardings=NamedSharding(self.mesh, POOL_SPEC))()

    def prefill(self, pool, ids, start, n_tok, bt, samp):
        toks, pool = self._prefill(self.params, pool, jnp.asarray(ids),
                                   jnp.asarray(start),
                                   jnp.asarray(n_tok), jnp.asarray(bt),
                                   samp)
        return self._my_row(toks), pool

    def decode(self, pool, tokens, pos, bt, lens, samp):
        toks, pool = self._decode(self.params, pool,
                                  jnp.asarray(tokens), jnp.asarray(pos),
                                  jnp.asarray(bt), jnp.asarray(lens),
                                  samp)
        return self._my_row(toks), pool

    def verify(self, pool, ids, start, n_tok, bt, samp):
        toks, pool = self._verify(self.params, pool, jnp.asarray(ids),
                                  jnp.asarray(start),
                                  jnp.asarray(n_tok), jnp.asarray(bt),
                                  samp)
        return self._my_row(toks), pool

    def set_params(self, params) -> None:
        # weight hot-swap flip: the smap-wrapped step functions take
        # params as an explicit argument, so the next tick's forwards
        # run the new generation with no re-trace (same as LocalExec)
        self.params = params

    def migrate(self, pool, migrations):
        migs = tuple(migrations)
        if migs not in self._migrate_cache:
            kv, name, tp = self.kv, self.kv.handle.name, self.tp

            def mg(pool):
                local = pool[0, 0]
                q = CommQueue(("data", "model"), {name: local},
                              transport=PermuteTransport())
                st = kv.issue_migrations(
                    q, local, migs,
                    pairs_of=lambda m: [(m.src_pe * tp + t,
                                         m.dst_pe * tp + t)
                                        for t in range(tp)])
                assert q.stats()["quiets"] == 1
                return st[name][None, None]

            self._migrate_cache[migs] = jax.jit(
                smap(mg, self.mesh, (POOL_SPEC,), POOL_SPEC))
        return self._migrate_cache[migs](pool)
