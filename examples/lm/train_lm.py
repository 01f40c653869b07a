"""Language-model training example (the long-context counterpart of the
reference's seq2seq example — ``examples/seq2seq/seq2seq.py`` — rebuilt
around the transformer zoo model and the native prefetching data layer).

Data-parallel over every visible device; flash attention on TPU; synthetic
character-level corpus (zero-egress environment), deterministic and
learnable.  Run single-chip, or simulate a pod:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/lm/train_lm.py --steps 60
"""

from __future__ import annotations

import argparse

import numpy as np


def make_corpus(n_tokens: int = 200_000, vocab: int = 64, seed: int = 0):
    """Order-2 Markov stream: predictable structure a small LM can learn."""
    rng = np.random.RandomState(seed)
    trans = rng.dirichlet(np.ones(vocab) * 0.05, size=(vocab, vocab))
    out = np.zeros(n_tokens, np.int32)
    out[0], out[1] = rng.randint(0, vocab, 2)
    for i in range(2, n_tokens):
        out[i] = rng.choice(vocab, p=trans[out[i - 2], out[i - 1]])
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch-per-chip", type=int, default=8)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--accum", type=int, default=1,
                    help="gradient-accumulation microbatches per step")
    ap.add_argument("--remat", action="store_true",
                    help="rematerialize decoder blocks (jax.checkpoint)")
    ap.add_argument("--zero", action="store_true",
                    help="ZeRO-shard params/grads/optimizer state 1/N")
    ap.add_argument("--optimizer", default="adamw",
                    choices=("adamw", "adafactor"),
                    help="adafactor = factored second moments, the "
                         "low-memory tier that put 1.5B-param training on "
                         "one 16 GB chip (result/lm_tpu_1558m.json)")
    ap.add_argument("--warmup", type=int, default=0,
                    help="linear-warmup steps into a cosine decay schedule")
    ap.add_argument("--eval", action="store_true",
                    help="after training, validation perplexity over a "
                         "held-out split via the multi-node evaluator")
    ap.add_argument("--generate", type=int, default=0,
                    help="after training, greedily generate N tokens from a "
                         "corpus prompt (KV-cache decode)")
    ap.add_argument("--pack", action="store_true",
                    help="train on packed variable-length documents "
                         "(segment-masked attention, per-doc positions)")
    ap.add_argument("--rope", action="store_true",
                    help="rotary position embeddings instead of the "
                         "learned table (no max_len cap)")
    ap.add_argument("--kv-heads", type=int, default=0,
                    help="grouped-query attention: kv heads (0 = classic "
                         "multi-head; must divide the 4 query heads)")
    ap.add_argument("--window", type=int, default=0,
                    help="sliding-window attention size (0 = full)")
    ap.add_argument("--param-dtype", default="float32",
                    choices=("float32", "bfloat16"),
                    help="parameter STORAGE dtype: bfloat16 halves "
                         "persistent params+grads HBM (T5-style; pairs "
                         "with --optimizer adafactor for >2B configs on "
                         "one chip)")
    ap.add_argument("--lora", type=int, default=0, metavar="RANK",
                    help="LoRA fine-tuning: freeze the base params after "
                         "init and train rank-RANK adapters on the "
                         "attention projections only (optimizer state, "
                         "grads and allreduce wire are adapter-sized); "
                         "--eval/--generate run on the merged export")
    args = ap.parse_args()
    if args.lora and args.zero:
        # ZeRO shards the OPTIMIZER tree; with LoRA that tree is the tiny
        # adapter set while the frozen base stays replicated — sharding
        # kilobytes defeats the point and materialize_params would return
        # adapters, not params.  Keep the tiers orthogonal.
        ap.error("--lora and --zero are mutually exclusive (the adapter "
                 "tree is too small to shard; the frozen base is "
                 "replicated either way)")
    if args.generate and 16 + args.generate > args.seq_len and not args.rope:
        # Fail fast, not after the whole training run: the 16-token prompt
        # plus the generated tokens must fit the learned table's max_len
        # (rope has no cap — lm_generate sizes the cache to the request).
        ap.error(f"--generate {args.generate} + 16-token prompt exceeds "
                 f"--seq-len {args.seq_len}")

    import jax

    if jax.default_backend() == "cpu":
        jax.config.update("jax_cpu_enable_async_dispatch", False)
    import jax.numpy as jnp
    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu.datasets import ArrayDataset, scatter_dataset
    from chainermn_tpu.iterators import PrefetchIterator
    from chainermn_tpu.models import TransformerLM, lm_loss

    comm = cmn.create_communicator("xla")
    vocab, T = 64, args.seq_len
    corpus = make_corpus()
    if args.pack:
        # Split the stream into variable-length documents and PACK them:
        # segment-masked attention + per-doc position restart (exactly the
        # variable-length story the reference's seq2seq bucketing solved by
        # padding, without the pad waste).
        from chainermn_tpu.datasets import pack_sequences, packing_efficiency

        rng = np.random.RandomState(7)
        docs, at = [], 0
        while at < len(corpus) - 4:
            L = int(rng.randint(T // 4, T + 1))
            docs.append(corpus[at : at + L])
            at += L
        tokens, targets, seg = pack_sequences(docs, seq_len=T)
        if jax.process_index() == 0:
            print(f"packed {len(docs)} docs into {len(tokens)} rows "
                  f"(fill {packing_efficiency(seg):.2f})")
        arrays = (tokens, targets, seg)
    else:
        n_seq = (len(corpus) - 1) // T
        tokens = corpus[: n_seq * T].reshape(n_seq, T)
        targets = corpus[1 : n_seq * T + 1].reshape(n_seq, T)
        arrays = (tokens, targets)
    # A REAL held-out split: validation rows are removed from the arrays
    # BEFORE the training dataset is built.
    n_val = max(len(arrays[0]) // 10, comm.size) if args.eval else 0
    if n_val >= len(arrays[0]):
        ap.error(
            f"--eval needs more data: {len(arrays[0])} rows can't spare a "
            f"{n_val}-row validation split (shorten --seq-len or drop --eval)"
        )
    val_arrays = tuple(a[-n_val:] for a in arrays) if n_val else None
    if n_val:
        arrays = tuple(a[:-n_val] for a in arrays)
    ds = scatter_dataset(  # host-level shard (process_index)
        ArrayDataset(*arrays), comm, shuffle=True, seed=0
    )
    # Re-wrap the local shard for the native prefetcher (one pass over the
    # shard, not one per column).
    shard_rows = ds[:]
    local = ArrayDataset(*[np.stack([row[i] for row in shard_rows])
                           for i in range(len(arrays))])
    global_batch = args.batch_per_chip * comm.size
    it = PrefetchIterator(local, global_batch, seed=1)
    # Device-side stage: next batches transfer while the current step runs.
    it = cmn.create_device_prefetch_iterator(it, comm, depth=2)

    model = TransformerLM(
        vocab=vocab, n_layers=args.layers, d_model=args.d_model,
        n_heads=4, d_ff=4 * args.d_model, max_len=T,
        dtype=jnp.float32 if jax.default_backend() == "cpu" else jnp.bfloat16,
        param_dtype=getattr(jnp, args.param_dtype),
        remat=args.remat,
        pos_enc="rope" if args.rope else "learned",
        n_kv_heads=args.kv_heads, window=args.window,
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, T), jnp.int32)
    )["params"]
    lr = (
        optax.warmup_cosine_decay_schedule(
            0.0, args.lr, args.warmup, max(args.steps, args.warmup + 1)
        )
        if args.warmup
        else args.lr
    )
    tx = (
        optax.adafactor(lr)
        if args.optimizer == "adafactor"
        else optax.adamw(lr, weight_decay=0.01)
    )
    # Schedules live INSIDE the optax chain (the jitted step), the TPU-native
    # form of the reference examples' ExponentialShift trainer extension.
    opt = (
        cmn.create_zero_optimizer(tx, comm)
        if args.zero
        else cmn.create_multi_node_optimizer(tx, comm)
    )
    if args.lora:
        from chainermn_tpu.models import (
            lora_init,
            lora_merge,
            lora_param_count,
            make_lora_loss,
        )

        base_params = params
        lora = lora_init(jax.random.PRNGKey(1), base_params, rank=args.lora)
        if jax.process_index() == 0:
            print(f"lora rank {args.lora}: {lora_param_count(lora)} "
                  f"trainable / {lora_param_count(base_params)} total "
                  "params")
        state = opt.init(lora)
        step = opt.make_train_step(
            make_lora_loss(lm_loss(model), base_params),
            has_aux=True, accum_steps=args.accum,
        )
    else:
        state = opt.init(params)
        step = opt.make_train_step(
            lm_loss(model), has_aux=True, accum_steps=args.accum
        )

    for i in range(args.steps):
        batch = next(it)
        # Batches arrive pre-sharded on device from the prefetch stage.
        state, metrics = step(state, batch)
        if i % 20 == 0 or i == args.steps - 1:
            if jax.process_index() == 0:
                print(f"step {i}: loss {float(metrics['loss']):.4f}",
                      flush=True)
    it.close()
    # One materialization serves both --eval and --generate (under ZeRO
    # this is a full cross-device param all-gather; don't repeat it).
    full_params = None
    if args.eval or args.generate:
        if args.lora:
            # Merged export: a plain params tree — eval and decode run
            # exactly as they would on a fully fine-tuned model.
            full_params = lora_merge(base_params, state.params)
        else:
            full_params = (
                opt.materialize_params(state) if args.zero else state.params
            )
    if args.eval:
        from chainermn_tpu.extensions import (
            Evaluator,
            create_multi_node_evaluator,
        )
        from chainermn_tpu.iterators import SerialIterator

        # The evaluator's multi-host contract: every process iterates the
        # same GLOBAL batches in lockstep; SerialIterator carries the fixed
        # batch_size so every batch (incl. the tail) pads to ONE compiled
        # shape.
        eval_bs = min(64, n_val)

        def val_batches():
            return SerialIterator(ArrayDataset(*val_arrays), eval_bs,
                                  repeat=False, shuffle=False)

        def metric_fn(params, batch):
            toks, tgts, *rest = batch  # packed batches carry segment ids
            logits = model.apply(
                {"params": params}, toks,
                segment_ids=rest[0] if rest else None,
            )
            m = (tgts >= 0).astype(jnp.float32)
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits, jnp.maximum(tgts, 0)
            )
            # Token-weighted sums; finalize divides AFTER the global psum —
            # the exact corpus perplexity, not a mean of batch means.
            return {"ce_sum": (ce * m).sum(-1), "tokens": m.sum(-1)}

        def finalize(sums, count):
            return {
                "val_ppl": jnp.exp(
                    sums["ce_sum"] / jnp.maximum(sums["tokens"], 1.0)
                ),
                "val_tokens": sums["tokens"],
            }

        ev = create_multi_node_evaluator(
            Evaluator(val_batches, metric_fn, comm, finalize=finalize), comm
        )
        scores = ev.evaluate(params=full_params)
        if jax.process_index() == 0:
            print(f"val_ppl {scores['val_ppl']:.3f}  "
                  f"({int(scores['val_tokens'])} tokens)", flush=True)
    if args.generate:
        from chainermn_tpu.models import lm_generate

        # Collective work (the ZeRO gather above) already ran on EVERY
        # process; only the host-local decode and printing are rank-0 gated
        # (mesh computations inside the guard would deadlock multi-host).
        gen_params = jax.device_get(full_params)
        if jax.process_index() == 0:
            prompt = jnp.asarray(corpus[:16][None].astype(np.int32))
            out = lm_generate(model, gen_params, prompt, args.generate)
            print("prompt:", corpus[:16].tolist())
            print("generated:", np.asarray(out)[0].tolist())
    return float(metrics["loss"])


if __name__ == "__main__":
    main()
