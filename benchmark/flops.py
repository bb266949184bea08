"""Operations and bytes the algorithms need, computed from shapes.

The yardstick's own arithmetic: copied from ``bench.py`` (BERT masked-MLM
formula; RN50 2*MAC over the conv/fc shapes) so that a later PR that edits
the program cannot move an MFU or a roofline share.  Recomputed operations
(rematerialisation, the backward's second forward of a fused kernel) are
never counted.  Everything here is a pure function of sizes.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Tuple

_HERE = os.path.dirname(os.path.abspath(__file__))


def load_peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of ``device_kind`` from ``peaks.json``; an unknown kind is an
    error, never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(
            f"device kind {device_kind!r} has no entry in benchmark/peaks.json"
            f" (known: {sorted(k for k in table if k != 'source')})")
    return dict(table[device_kind])


# -- BERT (masked-LM pre-training step) ---------------------------------------

def bert_mlm_train_flops_per_sample(hidden: int, layers: int, ffn: int,
                                    vocab: int, seq: int, n_masked: int
                                    ) -> float:
    """Forward+backward FLOPs of one sequence (bench.py:bench_bert_masked):
    6 per weight of the encoder matmuls (QKV, out: 4 d^2; FFN: 2 d F) per
    token, 6 V d per masked position for the head, and the attention
    score/context matmuls 12 L d T per token (2 matmuls x 2 T d MACs...
    forward 4 T d, x3 for forward+backward)."""
    enc = 6.0 * layers * (4 * hidden * hidden + 2 * hidden * ffn) * seq
    head = 6.0 * vocab * hidden * n_masked
    attn = 12.0 * layers * hidden * seq * seq
    return enc + head + attn


# -- ResNet-50 ----------------------------------------------------------------

def resnet50_conv_sites(image: int = 224, widths=(64, 128, 256, 512),
                        blocks=(3, 4, 6, 3), expansion: int = 4,
                        in_ch: int = 3) -> List[Dict[str, int]]:
    """Every convolution of ResNet-50 v1.5 (stride on the 3x3) as a dict
    {name, cin, cout, k, stride, hout (= wout)}, in forward order."""
    sites = []
    h = image // 2                                   # 7x7/2 stem
    sites.append(dict(name="stem", cin=in_ch, cout=64, k=7, stride=2, hout=h))
    h = h // 2                                       # 3x3/2 max pool
    cin = 64
    for stage, (w, n) in enumerate(zip(widths, blocks)):
        for b in range(n):
            stride = 2 if (b == 0 and stage > 0) else 1
            hin, hout = h, h // stride
            p = f"res{stage}_{b}"
            sites.append(dict(name=p + ".b0", cin=cin, cout=w, k=1, stride=1,
                              hout=hin))
            sites.append(dict(name=p + ".b1", cin=w, cout=w, k=3,
                              stride=stride, hout=hout))
            sites.append(dict(name=p + ".b2", cin=w, cout=w * expansion, k=1,
                              stride=1, hout=hout))
            if cin != w * expansion or stride != 1:
                sites.append(dict(name=p + ".short", cin=cin,
                                  cout=w * expansion, k=1, stride=stride,
                                  hout=hout))
            cin, h = w * expansion, hout
    return sites


def resnet50_forward_flops_per_sample(image: int = 224, classes: int = 1000
                                      ) -> float:
    """2*MAC over every convolution and the classifier (bench.py counts the
    same from the program's inferred shapes)."""
    fl = 0.0
    for s in resnet50_conv_sites(image):
        fl += 2.0 * s["cout"] * s["hout"] * s["hout"] * s["cin"] * s["k"] ** 2
    return fl + 2.0 * 2048 * classes


def resnet50_train_flops_per_sample(image: int = 224, classes: int = 1000
                                    ) -> float:
    """Forward + backward = 3x forward (bench.py:bench_resnet50)."""
    return 3.0 * resnet50_forward_flops_per_sample(image, classes)


# -- rooflines ----------------------------------------------------------------

def roofline_seconds(flops: float, nbytes: float, peaks: Dict[str, float]
                     ) -> Tuple[float, str]:
    """Least time the chip could take and which bound sets it."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


# -- GPT-1 decode -------------------------------------------------------------

def gpt_decode_flops_per_token(hidden: int, layers: int, ffn: int, vocab: int,
                               context: int) -> float:
    """Forward FLOPs to process one token at ``context`` attended positions:
    2 per weight of the layer matmuls, the head, and 4 d per attended
    position per layer (scores + context)."""
    return (2.0 * layers * (4 * hidden * hidden + 2 * hidden * ffn)
            + 2.0 * vocab * hidden + 4.0 * layers * hidden * context)
