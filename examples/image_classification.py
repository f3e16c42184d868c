"""Paper §5.1: ODE-block image classification (SqueezeNext-style block with
the conv vector field), trained with selectable adjoint policies on a
synthetic CIFAR-10 stand-in (the dataset is not available offline; shapes,
batch and class count match).

  PYTHONPATH=src python examples/image_classification.py [--steps 100] \
      [--adjoint pnode] [--method rk4] [--n-steps 4]
"""
import argparse
import time

import jax

from repro.core.depth_ode import ODEBlock
from repro.models.ode_nets import (classifier_apply, classifier_init,
                                   conv_vf, make_classifier_step,
                                   synthetic_cifar)
from repro.optim.adamw import AdamW


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--adjoint", default="pnode")
    ap.add_argument("--method", default="rk4")
    ap.add_argument("--n-steps", type=int, default=2)
    ap.add_argument("--ncheck", type=int, default=2)
    ap.add_argument("--channels", type=int, default=8)
    args = ap.parse_args()

    kw = {"ncheck": args.ncheck} if args.adjoint.startswith("revolve") else {}
    block = ODEBlock(conv_vf, n_steps=args.n_steps, method=args.method,
                     adjoint=args.adjoint, **kw)
    params = classifier_init(jax.random.PRNGKey(0), channels=args.channels)
    opt = AdamW(lr=2e-3, warmup_steps=10, total_steps=args.steps)
    state = opt.init(params)
    step_fn = make_classifier_step(block, opt)

    key = jax.random.PRNGKey(1)
    t0 = time.time()
    for step in range(args.steps):
        key, sub = jax.random.split(key)
        x, labels = synthetic_cifar(sub, args.batch)
        params, state, loss, logits, _ = step_fn(params, state, x, labels)
        if step % max(1, args.steps // 10) == 0:
            acc = float((logits.argmax(-1) == labels).mean())
            print(f"step {step:4d} loss {float(loss):.4f} acc {acc:.3f} "
                  f"({(time.time()-t0)/(step+1)*1e3:.0f} ms/step)")

    x, labels = synthetic_cifar(jax.random.PRNGKey(99), 512)
    logits = jax.jit(lambda p, x: classifier_apply(
        p, x, odeint_fn=lambda vf, u, th: block(u, th)))(params, x)
    print(f"eval accuracy: {float((logits.argmax(-1) == labels).mean()):.3f} "
          f"(adjoint={args.adjoint}, method={args.method})")


if __name__ == "__main__":
    main()
