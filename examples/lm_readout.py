"""DFR readout at scale: the paper's online trainer as an LM adaptation head.

    PYTHONPATH=src python examples/lm_readout.py

A frozen LM backbone (reduced smollm here; any of the 10 archs via --arch)
emits hidden-state streams; the modular DFR + DPRR + streaming Ridge solve
adapts a classification head ONLINE, with (A, B) reduced across data shards
by one psum (exact, because Eq. 38 is an associative sum) - the edge system
of the paper, lifted to a pod.  Demonstrated here on a synthetic sequence
classification task with a shard_map over the host mesh.
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_reduced
from repro.core.readout import DistributedDFRReadout, ReadoutConfig
from repro.models.transformer import Transformer


def synth_task(key, n, t, vocab, n_classes):
    """Class c = sequences biased toward token block c (linearly separable
    in occupancy, but only through temporal features here)."""
    ks = jax.random.split(key, 3)
    labels = jax.random.randint(ks[0], (n,), 0, n_classes)
    block = vocab // n_classes
    base = jax.random.randint(ks[1], (n, t), 0, vocab)
    biased = block * labels[:, None] + jax.random.randint(ks[2], (n, t), 0, block)
    pick = jax.random.bernoulli(ks[0], 0.6, (n, t))
    toks = jnp.where(pick, biased, base)
    return toks.astype(jnp.int32), labels.astype(jnp.int32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--n", type=int, default=64)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--classes", type=int, default=4)
    args = ap.parse_args()

    cfg = get_reduced(args.arch)
    model = Transformer(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    print(f"frozen backbone: {cfg.name} (reduced, d_model={cfg.d_model})")

    toks, labels = synth_task(jax.random.PRNGKey(1), args.n, args.seq,
                              cfg.vocab, args.classes)

    @jax.jit
    def hidden(toks):
        """Frozen-backbone features: the trunk output before unembedding."""
        from repro.models.layers import embed_lookup
        x = embed_lookup(params["embed"], toks)
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        h, _ = model._trunk(params, x)
        return h.astype(jnp.float32)  # (B, T, d_model)

    h = hidden(toks)

    rc = ReadoutConfig(feature_dim=cfg.d_model, n_classes=args.classes,
                       n_nodes=30)
    mesh = jax.make_mesh((jax.device_count(),), ("data",))
    ro = DistributedDFRReadout(rc, axis_names=("data",))
    dfr_params, ridge_state = ro.init()

    def fit_shard(h, labels):
        st = ro.accumulate(ridge_state, dfr_params, h, labels)
        fitted = ro.solve(st, dfr_params, jnp.float32(1e-2))
        return fitted.W, fitted.b

    W, b = jax.shard_map(fit_shard, mesh=mesh,
                         in_specs=(P("data"), P("data")), out_specs=P())(h, labels)
    fitted = type(dfr_params)(p=dfr_params.p, q=dfr_params.q, W=W, b=b)
    preds = ro.predict(fitted, h)
    acc = float(jnp.mean((preds == labels).astype(jnp.float32)))
    print(f"DFR readout (one distributed ridge solve, {args.n} sequences): "
          f"train acc {acc:.3f} over {args.classes} classes")
    print("the same code path scales: (A,B) psum crosses 'data' (+'pod') "
          "axes; the Cholesky system is s x s = "
          f"{rc.n_nodes**2 + rc.n_nodes + 1}^2 regardless of stream length")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
