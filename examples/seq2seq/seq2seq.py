#!/usr/bin/env python
"""Seq2seq NMT — the reference's ``examples/seq2seq/seq2seq.py`` re-designed
for static shapes: bucketed/padded variable-length batches with a masked
loss, data-parallel allreduce, multi-node-evaluator-style token accuracy.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/seq2seq/seq2seq.py --force-cpu --epoch 2
"""

import argparse

import jax


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--communicator", default="pure_nccl")
    p.add_argument("--batchsize", type=int, default=64)
    p.add_argument("--epoch", type=int, default=3)
    p.add_argument("--vocab", type=int, default=50)
    p.add_argument("--embed", type=int, default=64)
    p.add_argument("--hidden", type=int, default=128)
    # width 4 keeps non-pad fraction ≥ 0.85 on the synthetic task (the
    # BASELINE.md "> 80% non-pad tokens" target) at ~the same batch count.
    p.add_argument("--bucket-width", type=int, default=4)
    p.add_argument("--arch", default="lstm", choices=["lstm", "transformer"],
                   help="lstm = reference-parity encoder-decoder; "
                        "transformer = flash cross-attention tier")
    p.add_argument("--packed", action="store_true",
                   help="pack several pairs per fixed-shape row "
                        "(datasets.pack_pairs; transformer arch only) "
                        "instead of bucketing — trades the bucketed tier's "
                        "pad waste for per-pair segment isolation")
    p.add_argument("--pack-len", type=int, default=64,
                   help="row width (both sides) for --packed")
    p.add_argument("--data-npz", default=None,
                   help="on-disk corpus in save_translation_npz's offsets "
                        "format (the reference's WMT file role); the last "
                        "1/8 of pairs becomes the validation split")
    p.add_argument("--force-cpu", action="store_true")
    args = p.parse_args()

    if args.force_cpu:
        jax.config.update("jax_platforms", "cpu")
        # avoid in-process CPU collective rendezvous deadlocks (see tests/conftest.py)
        jax.config.update("jax_cpu_enable_async_dispatch", False)

    import numpy as np
    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu.datasets.seq import bucket_batches, make_synthetic_translation
    from chainermn_tpu.models import (
        Seq2Seq,
        TransformerSeq2Seq,
        greedy_decode,
        seq2seq_loss,
    )

    comm = cmn.create_communicator(args.communicator)
    if args.arch == "transformer":
        # --embed = d_model, --hidden = FFN width (both flags meaningful
        # in either arch).
        model = TransformerSeq2Seq(
            vocab_src=args.vocab, vocab_tgt=args.vocab,
            d_model=args.embed, n_heads=4, d_ff=max(args.hidden, args.embed),
        )
    else:
        model = Seq2Seq(vocab_src=args.vocab, vocab_tgt=args.vocab,
                        embed=args.embed, hidden=args.hidden,
                        axis_name=comm.axis_name)
    if args.data_npz:
        from chainermn_tpu.datasets.seq import load_translation_npz

        all_pairs = load_translation_npz(args.data_npz)
        n_val = max(len(all_pairs) // 8, 1)
        pairs, val_pairs = all_pairs[:-n_val], all_pairs[-n_val:]
        hi = max(max(w for s, t in all_pairs for w in list(s) + list(t)), 0)
        if hi >= args.vocab:
            raise SystemExit(
                f"--data-npz contains token id {hi} >= --vocab {args.vocab}"
            )
    else:
        pairs = make_synthetic_translation(4096, vocab=args.vocab, min_len=4,
                                           max_len=16)
        val_pairs = None
    if args.packed:
        if args.arch != "transformer":
            raise SystemExit("--packed needs --arch transformer (the LSTM "
                             "tier has no segment-isolated attention)")
        from chainermn_tpu.datasets import pack_pairs, packing_efficiency

        src, tgt, sseg, tseg = pack_pairs(pairs, args.pack_len,
                                          args.pack_len)
        # Efficiency BEFORE the batch-rounding pad rows below — those are
        # a row-count artifact, not pack_pairs quality.
        eff = packing_efficiency(tseg)
        # Pad the ROW count to full batches (zero rows are all-pad: seg 0,
        # masked out of the loss) so every pair trains under ONE compiled
        # shape — the packing analog of bucket_batches' keep_tail.
        B = args.batchsize
        n_rows = ((len(src) + B - 1) // B) * B
        pad_rows = n_rows - len(src)
        src, tgt, sseg, tseg = (
            np.concatenate([a, np.zeros((pad_rows, a.shape[1]), a.dtype)])
            for a in (src, tgt, sseg, tseg)
        )
        batches = [
            (src[i:i + B], tgt[i:i + B], sseg[i:i + B], tseg[i:i + B])
            for i in range(0, n_rows, B)
        ]
        if jax.process_index() == 0:
            print(f"devices: {comm.size}  packed: {len(batches)} batches  "
                  f"packing efficiency: {eff:.2f}")
    else:
        batches = bucket_batches(pairs, args.batchsize,
                                 bucket_width=args.bucket_width)
        if jax.process_index() == 0:
            nonpad = float(np.mean([(b[0] != 0).mean() for b in batches]))
            print(f"devices: {comm.size}  buckets: {len(batches)} batches  "
                  f"non-pad fraction: {nonpad:.2f}")

    src0, tgt0 = batches[0][:2]
    params = model.init(jax.random.PRNGKey(0), src0[:2], tgt0[:2])["params"]
    opt = cmn.create_multi_node_optimizer(optax.adam(3e-3), comm)
    state = opt.init(params)
    loss_fn = seq2seq_loss(model)

    for epoch in range(1, args.epoch + 1):
        losses, accs = [], []
        for b in batches:
            state, m = opt.update(state, b, loss_fn, has_aux=True)
            losses.append(m["loss"])
            accs.append(m["token_accuracy"])
        if jax.process_index() == 0:
            print(f"epoch {epoch}  loss {np.mean([float(l) for l in losses]):.4f}  "
                  f"token_acc {np.mean([float(a) for a in accs]):.4f}",
                  flush=True)

    # Corpus BLEU via the multi-node evaluator (reference: "BLEU eval via
    # multi-node evaluator", SURVEY.md §2.9): greedy-decode inside the jitted
    # eval step, sum the clipped n-gram stats exactly across devices/batches
    # (and processes), finalize once.
    from chainermn_tpu.extensions import (
        Evaluator,
        bleu_finalize,
        bleu_stats,
        create_multi_node_evaluator,
    )

    if val_pairs is None:
        val_pairs = make_synthetic_translation(512, vocab=args.vocab,
                                               min_len=4, max_len=16,
                                               seed=99)
    val_batches = bucket_batches(val_pairs, args.batchsize,
                                 bucket_width=args.bucket_width,
                                 keep_tail=True)

    def bleu_metric(params, batch):
        src, tgt = batch
        pred = greedy_decode(model, params, src, max_len=tgt.shape[1])
        return bleu_stats(pred, tgt)

    ev = create_multi_node_evaluator(
        Evaluator(lambda: iter(val_batches), bleu_metric, comm,
                  finalize=bleu_finalize),
        comm,
    )
    scores = ev.evaluate(state.params)
    if jax.process_index() == 0:
        print(f"corpus BLEU {scores['bleu']:.2f}  "
              f"({int(scores['n_sentences'])} sentences)", flush=True)
        out = greedy_decode(model, jax.device_get(state.params), src0[:4],
                            max_len=src0.shape[1])
        print("sample src :", src0[0][src0[0] != 0])
        print("sample pred:", np.asarray(out[0]))


if __name__ == "__main__":
    main()
