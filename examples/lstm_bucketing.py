"""LSTM language model with bucketing (reference: example/rnn/lstm_bucketing.py:
BucketSentenceIter + BucketingModule + per-bucket unrolled LSTM, Perplexity
metric). Reads a tokenized text file via --data; synthetic corpus fallback.
"""
import argparse
import logging

import numpy as np

import mxnet_tpu as mx


def tokenize_text(fname, vocab=None):
    with open(fname) as f:
        lines = [l.strip().split() for l in f if l.strip()]
    if vocab is None:
        vocab = {"<pad>": 0, "<unk>": 1}
        for l in lines:
            for w in l:
                vocab.setdefault(w, len(vocab))
    sent = [[vocab.get(w, 1) for w in l] for l in lines]
    return sent, vocab


def synthetic_corpus(n=500, vmax=100, seed=0):
    rng = np.random.RandomState(seed)
    return [list(rng.randint(2, vmax, rng.randint(5, 60))) for _ in range(n)], \
        {str(i): i for i in range(vmax)}


def stdlib_corpus(vocab_size=10000, max_sentences=None):
    """~1M words of real English: the Python standard library's docstrings
    (available offline everywhere). Lines become sentences; the top
    ``vocab_size`` words keep their identity, the rest map to <unk> —
    the PTB-style preprocessing of the reference's rnn examples."""
    import importlib
    import inspect
    import re
    import sys
    import warnings

    warnings.filterwarnings("ignore")
    texts = []
    # STDLIB modules only (sys.stdlib_module_names) — iterating site-packages
    # would import third-party code (including jax backend plugins, which
    # must not be imported as plain modules)
    for name in sorted(sys.stdlib_module_names):
        if name.startswith("_") or name in (
                "antigravity", "this", "idlelib", "tkinter", "turtle",
                "turtledemo"):
            continue
        try:
            mod = importlib.import_module(name)
        except Exception:  # noqa: BLE001 - optional modules may not import
            continue
        if mod.__doc__:
            texts.append(mod.__doc__)
        for obj_name, obj in list(vars(mod).items()):
            if obj_name.startswith("_"):
                continue
            try:
                doc = inspect.getdoc(obj)
            except Exception:  # noqa: BLE001
                continue
            if doc:
                texts.append(doc)
    word_re = re.compile(r"[a-z']+")
    lines = []
    for t in texts:
        for line in t.lower().splitlines():
            words = word_re.findall(line)
            if len(words) >= 4:
                lines.append(words)
    counts = {}
    for l in lines:
        for w in l:
            counts[w] = counts.get(w, 0) + 1
    keep = sorted(counts, key=counts.get, reverse=True)[: vocab_size - 2]
    vocab = {"<pad>": 0, "<unk>": 1}
    for w in keep:
        vocab[w] = len(vocab)
    sentences = [[vocab.get(w, 1) for w in l] for l in lines]
    if max_sentences:
        sentences = sentences[:max_sentences]
    return sentences, vocab


def main():
    logging.basicConfig(level=logging.INFO)
    ap = argparse.ArgumentParser()
    ap.add_argument("--data", default=None, help="tokenized text file")
    ap.add_argument("--num-hidden", type=int, default=200)
    ap.add_argument("--num-embed", type=int, default=200)
    ap.add_argument("--num-layers", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--num-epochs", type=int, default=5)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--stdlib-corpus", action="store_true",
                    help="train on ~1M words of real English (python stdlib "
                         "docstrings) instead of the synthetic corpus")
    ap.add_argument("--max-sentences", type=int, default=None)
    ap.add_argument("--valid-frac", type=float, default=0.0,
                    help="hold out this sentence fraction and report "
                         "validation perplexity per epoch")
    args = ap.parse_args()

    ctx = mx.tpu() if mx.context.num_tpus() else mx.cpu()
    logging.info("training on %s", ctx)

    buckets = [10, 20, 30, 40, 60]
    if args.data:
        sentences, vocab = tokenize_text(args.data)
    elif args.stdlib_corpus:
        sentences, vocab = stdlib_corpus(max_sentences=args.max_sentences)
        logging.info("stdlib corpus: %d sentences, %d words, vocab %d",
                     len(sentences), sum(len(s) for s in sentences),
                     len(vocab))
    else:
        sentences, vocab = synthetic_corpus()
    vocab_size = max(max(max(s) for s in sentences) + 1, len(vocab))

    val = None
    if args.valid_frac > 0:
        rng = np.random.RandomState(42)
        order = rng.permutation(len(sentences))
        n_val = int(len(sentences) * args.valid_frac)
        val_sent = [sentences[i] for i in order[:n_val]]
        sentences = [sentences[i] for i in order[n_val:]]
        val = mx.rnn.BucketSentenceIter(val_sent, args.batch_size,
                                        buckets=buckets)
        # context baseline: a unigram model of the TRAIN distribution
        # evaluated on the held-out tokens (what the LSTM must beat)
        counts = np.ones(vocab_size)
        for s in sentences:
            for w in s:
                counts[w] += 1
        p = counts / counts.sum()
        val_tokens = [w for s in val_sent for w in s]
        unigram_ppl = float(np.exp(-np.mean(np.log(p[val_tokens]))))
        logging.info("unigram baseline val perplexity: %.1f (uniform: %d)",
                     unigram_ppl, vocab_size)

    train = mx.rnn.BucketSentenceIter(sentences, args.batch_size, buckets=buckets)

    cell = mx.rnn.SequentialRNNCell()
    for i in range(args.num_layers):
        cell.add(mx.rnn.LSTMCell(num_hidden=args.num_hidden, prefix="lstm_l%d_" % i))

    def sym_gen(seq_len):
        data = mx.sym.Variable("data")
        label = mx.sym.Variable("softmax_label")
        embed = mx.sym.Embedding(data, input_dim=vocab_size,
                                 output_dim=args.num_embed, name="embed")
        outputs, _ = cell.unroll(seq_len, inputs=embed, merge_outputs=True)
        pred = mx.sym.Reshape(outputs, shape=(-1, args.num_hidden))
        pred = mx.sym.FullyConnected(pred, num_hidden=vocab_size, name="pred")
        label = mx.sym.Reshape(label, shape=(-1,))
        pred = mx.sym.SoftmaxOutput(pred, label=label, name="softmax")
        return pred, ("data",), ("softmax_label",)

    mod = mx.mod.BucketingModule(sym_gen, default_bucket_key=train.default_bucket_key,
                                 context=ctx)
    # pad id 0 is excluded from the perplexity (both corpora reserve it)
    mod.fit(train, eval_data=val, num_epoch=args.num_epochs,
            optimizer="sgd",
            optimizer_params={"learning_rate": args.lr, "momentum": 0.9},
            initializer=mx.init.Xavier(),
            batch_end_callback=[mx.callback.Speedometer(args.batch_size, 50)],
            eval_metric=mx.metric.Perplexity(ignore_label=0))


if __name__ == "__main__":
    main()
