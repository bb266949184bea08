"""BERT-base step-time attribution on the real chip (round-3: close the
43.6 → ≥45% MFU gap with the remaining loss itemized — VERDICT r2 #2).

Same timing discipline as rn50_ablate.py (steps chained on device, one
closing host sync)."""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from rn50_ablate import timed  # noqa


def bert_build(batch=128, seq=128, train=True, dropout=None, adam=True,
               fused_head=True, nlayer=12, fused_adam=False,
               fused_max_numel=None):
    import paddle_tpu as pt
    from paddle_tpu import optimizer as opt
    from paddle_tpu.models import transformer as T

    def build():
        cfg = T.BertConfig(n_layer=nlayer)
        feeds, logits, loss = T.build_bert_pretrain(
            cfg, seq, fused_head=fused_head, arange_pos=True,
            dropout=dropout)
        if train:
            o = opt.AdamOptimizer(1e-4, fused_flat=fused_adam,
                                  fused_max_numel=fused_max_numel) \
                if adam else opt.SGDOptimizer(1e-4)
            pt.amp.decorate(o).minimize(loss)
        else:
            pt.amp.enable()
        return loss

    def feed_fn():
        rng = np.random.RandomState(0)
        cfg_vocab = 30522
        return {
            "src_ids": rng.randint(1, cfg_vocab,
                                   (batch, seq)).astype(np.int32),
            "lm_label": rng.randint(0, cfg_vocab,
                                    (batch, seq)).astype(np.int32),
        }
    return build, feed_fn


def main():
    results = {}

    def run(name, steps=48, **kw):
        b, f = bert_build(**kw)
        dt, l0, lN = timed(b, f, steps=steps)
        results[name] = round(dt * 1000, 2)
        print(f"{name:32s} {dt*1000:8.2f} ms/step   loss {l0:.3f}->{lN:.3f}",
              flush=True)

    run("base_b128s128")                       # reproduce 126.7
    run("fwd_only", train=False)
    run("no_dropout", dropout=0.0)
    run("sgd_not_adam", adam=False)
    run("layers6", nlayer=6)                   # encoder share (linear part)
    run("seq256_b64", batch=64, seq=256)
    print(json.dumps(results))


if __name__ == "__main__":
    main()
