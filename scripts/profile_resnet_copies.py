"""Profile one ResNet-50 training step and itemize the layout-change
`copy`/`transpose` device time (VERDICT r4 item 4: the last 5% of
addressable step time — either recover it or close the memory-bound
case with this data).

Uses the traced timeline (jax.profiler -> merged chrome JSON) and sums
device-lane complete events by bucket: copy, transpose, fusion,
convolution, other. Prints per-bucket ms plus the N largest individual
copy/transpose ops with their durations, then one JSON line.

Env: BENCH_BATCH (256), BENCH_STEM (space_to_depth), BENCH_STEPS (3).
"""

import json
import os
import sys
import tempfile

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu import model_zoo


def main():
    assert jax.devices()[0].platform == "tpu", "profile on the chip"
    batch = int(os.environ.get("BENCH_BATCH", "256"))
    stem = os.environ.get("BENCH_STEM", "space_to_depth")
    steps = int(os.environ.get("BENCH_STEPS", "3"))

    model = model_zoo.ResNet50(dtype=jnp.bfloat16, stem=stem)
    rng = jax.random.PRNGKey(0)
    images = jnp.asarray(
        np.random.default_rng(0).uniform(size=(batch, 224, 224, 3)),
        jnp.bfloat16,
    )
    labels = jnp.zeros((batch,), jnp.int32)
    variables = jax.jit(lambda: model.init(rng, images, train=False))()
    params, batch_stats = variables["params"], variables["batch_stats"]
    opt = optax.sgd(0.01, momentum=0.9)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, batch_stats, opt_state, images, labels):
        def loss_fn(p):
            logits, mut = model.apply(
                {"params": p, "batch_stats": batch_stats},
                images,
                train=True,
                mutable=["batch_stats"],
            )
            one = jax.nn.one_hot(labels, logits.shape[-1])
            return (
                -jnp.mean(
                    jnp.sum(
                        jax.nn.log_softmax(
                            logits.astype(jnp.float32)
                        )
                        * one,
                        axis=-1,
                    )
                ),
                mut["batch_stats"],
            )

        (loss, bs), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params
        )
        upd, opt_state = opt.update(grads, opt_state)
        return optax.apply_updates(params, upd), bs, opt_state, loss

    # warm/compile outside the trace
    params, batch_stats, opt_state, loss = step(
        params, batch_stats, opt_state, images, labels
    )
    from _benchlib import sync

    sync(loss)

    path = os.path.join(tempfile.mkdtemp(), "resnet_profile.json")
    hvd.start_timeline(path, traced=True)
    for _ in range(steps):
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, images, labels
        )
    sync(loss)
    hvd.stop_timeline()

    events = json.load(open(path))["traceEvents"]
    buckets = {}
    tops = []
    for ev in events:
        if ev.get("ph") != "X" or not ev.get("dur"):
            continue
        name = str(ev.get("name", ""))
        low = name.lower()
        if low.startswith("end:"):
            continue
        if "copy" in low:
            b = "copy"
        elif "transpose" in low:
            b = "transpose"
        elif "convolution" in low or "conv" in low:
            b = "convolution"
        elif "fusion" in low:
            b = "fusion"
        else:
            b = "other"
        buckets[b] = buckets.get(b, 0.0) + ev["dur"] / 1e3
        if b in ("copy", "transpose"):
            tops.append((ev["dur"] / 1e3, name))

    per_step = {k: round(v / steps, 3) for k, v in buckets.items()}
    print("== per-step ms by bucket (over", steps, "steps):")
    for k, v in sorted(per_step.items(), key=lambda kv: -kv[1]):
        print(f"  {k:14s} {v:8.3f} ms")
    print("== largest copy/transpose ops (ms, name):")
    for dur, name in sorted(tops, reverse=True)[:15]:
        print(f"  {dur:8.3f}  {name}")
    print(
        json.dumps(
            {
                "metric": "resnet50_copy_profile",
                "value": per_step.get("copy", 0.0),
                "unit": "ms_copy_per_step",
                "batch": batch,
                "stem": stem,
                "buckets_ms": per_step,
                "platform": "tpu",
            }
        )
    )


if __name__ == "__main__":
    main()
