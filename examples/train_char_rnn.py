"""char-rnn async-DP training demo (BASELINE config 2: "char-rnn param sync,
4 peers, approximate-delta compression on").

Two modes:

- pod (default): N peers as devices on one mesh, compressed sync over ICI —
  `python examples/train_char_rnn.py corpus.txt --peers 4`
  (on CPU, prefix JAX_PLATFORMS=cpu and the 8-device XLA flag; on a v5e-8
  each peer is a real chip).
- peer: one process per worker over the TCP tree, reference-style —
  `python examples/train_char_rnn.py corpus.txt --peer 127.0.0.1:50000`
  run in multiple terminals; first becomes master.
"""

import argparse
import pathlib
import sys
import time

import jax
import jax.numpy as jnp

from shared_tensor_tpu.models import char_rnn as m
from shared_tensor_tpu.utils.compile_cache import enable_compile_cache


def train_pod(text: bytes, cfg, args) -> None:
    from shared_tensor_tpu.parallel.mesh import make_mesh
    from shared_tensor_tpu.train import PodTrainer

    n = args.peers
    mesh = make_mesh(n, 1)
    params = m.init_params(jax.random.key(0), cfg)
    tr = PodTrainer(
        mesh, params, lambda p, b: m.loss_fn(p, b, cfg), overlap=args.overlap
    )
    data = m.encode_corpus(text)
    print(f"{cfg.param_count} params, {n} peers, backend={jax.default_backend()}")
    t0 = time.perf_counter()
    for i in range(args.steps):
        batch = tr.shard_batch(
            m.make_batches(data, args.batch, args.seq, jax.random.key(i), n_peer=n)
        )
        losses, scales = tr.step(batch, lr=args.lr)
        if i % 20 == 0 or i == args.steps - 1:
            toks = (i + 1) * n * args.batch * args.seq
            print(
                f"step {i:4d} loss {float(jnp.mean(losses)):.3f} "
                f"spread {tr.replica_spread():.2e} "
                f"tok/s {toks / (time.perf_counter() - t0):.0f}"
            )
    prompt = jnp.frombuffer(text[:16], dtype=jnp.uint8).astype(jnp.int32)
    out = m.sample(tr.read(0), jax.random.key(1), prompt, cfg, length=200, temperature=0.8)
    print("--- sample ---")
    print((text[:16] + bytes(int(t) % 256 for t in out)).decode(errors="replace"))


def train_peer(text: bytes, cfg, args) -> None:
    from shared_tensor_tpu.comm.peer import create_or_fetch

    host, port = args.peer.rsplit(":", 1)
    params = m.init_params(jax.random.key(0), cfg)
    data = m.encode_corpus(text)
    grad = jax.jit(jax.grad(lambda p, b: m.loss_fn(p, b, cfg)))
    with create_or_fetch(host, int(port), params) as st:
        t0 = time.perf_counter()
        for i in range(args.steps):
            params = st.read()
            batch = m.make_batches(data, args.batch, args.seq, jax.random.key(i))
            g = grad(params, batch)
            st.add(jax.tree.map(lambda x: -args.lr * x, g))
            if i % 20 == 0:
                loss = float(m.loss_fn(params, batch, cfg))
                print(f"step {i:4d} loss {loss:.3f} {st.metrics(canonical=True)}")
        print(f"done in {time.perf_counter() - t0:.1f}s; final metrics {st.metrics(canonical=True)}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("corpus", nargs="?", help="text file (default: built-in pangram)")
    ap.add_argument("--peers", type=int, default=4)
    ap.add_argument("--peer", help="host:port — join/seed the TCP tree instead of a pod mesh")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.5)
    ap.add_argument("--hidden", type=int, default=512)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument(
        "--overlap", action="store_true",
        help="schedule the ICI sync collective under the backward pass",
    )
    args = ap.parse_args()

    if args.corpus:
        text = pathlib.Path(args.corpus).read_bytes()
    else:
        text = b"The quick brown fox jumps over the lazy dog. " * 2000
    if len(text) < args.seq + 2:
        sys.exit("corpus too small for --seq")

    enable_compile_cache()
    cfg = m.CharRNNConfig(hidden=args.hidden, layers=args.layers)
    if args.peer:
        train_peer(text, cfg, args)
    else:
        train_pod(text, cfg, args)


if __name__ == "__main__":
    main()
