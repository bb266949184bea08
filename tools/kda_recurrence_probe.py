"""Which side of the Ling cell's float32 comparison is off on the chip: one
head of the gated delta rule over 8192 positions (keys and values as a KDA
layer makes them, the log-decay as the bounded gate gives it at fresh weights
for a slow, a middling and a fast head) by

* numpy float64 on the host, token by token: the truth;
* the reference's float32 step on the device with ``Diag(exp(g)) S``;
* the same with the decay written ``1 + expm1(g)``;
* ``kda_chunked`` (the program's plain ``jax.numpy`` form) on the device.

And what the device's ``exp`` returns next to 0, where a recurrence that never
forgets compounds it 8192 times.

    chiprun -- python3 tools/kda_recurrence_probe.py
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def truth(q, k, v, g, beta):
    import numpy as np
    s = np.zeros((q.shape[1], v.shape[1]))
    out = np.zeros_like(v)
    for t in range(q.shape[0]):
        s = s * np.exp(g[t])[:, None]
        s = s + beta[t] * np.outer(k[t], v[t] - k[t] @ s)
        out[t] = q[t] @ s
    return out


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import kda_ops
    t, d = 8192, 128
    r = np.random.RandomState(0)

    def silu(x):
        return x / (1 + np.exp(-x))

    def unit(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    def recurrence(decay):
        """The reference's step with ``decay(g)`` for ``exp(g)``."""
        def run(q, k, v, g, beta):
            def one(state, x):
                q_t, k_t, v_t, g_t, b_t = x
                state = state * decay(g_t)[:, None]
                err = v_t - jnp.sum(state * k_t[:, None], axis=0)
                state = state + (b_t * k_t)[:, None] * err[None, :]
                return state, jnp.sum(state * q_t[:, None], axis=0)
            return jax.lax.scan(one, jnp.zeros((d, d), jnp.float32),
                                (q, k, v, g, beta))[1]
        return jax.jit(run)

    out = {"device": jax.devices()[0].device_kind,
           "exp_next_to_0": {str(x): float(jnp.exp(jnp.float32(x)) - 1.0)
                             for x in (0.0, -1e-20, -1e-10, -1e-8, -1e-7,
                                       -1e-6, -1e-5, -1e-4)},
           "expm1_next_to_0": {str(x): float(jnp.expm1(jnp.float32(x)))
                               for x in (-1e-20, -1e-10, -1e-7, -1e-5)},
           "heads": []}
    q = unit(silu(r.randn(t, d) * 0.6)) * d ** -0.5
    k = unit(silu(r.randn(t, d) * 0.6))
    v = silu(r.randn(t, d) * 0.6)
    beta = 1 / (1 + np.exp(-r.randn(t)))
    dt = np.exp(r.uniform(np.log(1e-3), np.log(1e-1), d))
    pre = r.randn(t, d) * 0.63 + dt + np.log(-np.expm1(-dt))
    # a head's rate exp(A_log) is drawn in (1, 16): a slow, a middling and
    # a fast head
    for rate in (1.5, 4.0, 12.0):
        g = -5.0 / (1 + np.exp(-rate * pre))
        args = [a.astype(np.float32) for a in (q, k, v, g, beta)]
        want = truth(*(a.astype(np.float64) for a in args))

        def rel(a):
            a = np.asarray(a, np.float64)
            return float(np.linalg.norm(a - want) / np.linalg.norm(want))

        f32 = [jnp.asarray(a) for a in args]
        with jax.default_matmul_precision("highest"):
            chunked = kda_ops.kda_chunked(
                *(a[None, :, None] for a in (f32[0] * d ** 0.5, *f32[1:4])),
                f32[4][None, :, None], chunk=64)[0, :, 0]
            out["heads"].append({
                "rate": rate, "g_median": float(np.median(g)),
                "token_by_token_exp": rel(recurrence(jnp.exp)(*f32)),
                "token_by_token_1_plus_expm1": rel(recurrence(
                    lambda x: 1.0 + jnp.expm1(x))(*f32)),
                "kda_chunked": rel(chunked)})
    print(json.dumps(out), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "kda_recurrence_probe.jsonl"),
              "a") as f:
        f.write(json.dumps(out) + "\n")


if __name__ == "__main__":
    main()
