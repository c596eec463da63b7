"""Serving example: batched prefill + KV-cache decode with the unified LM.

    PYTHONPATH=src python examples/serve_lm.py --arch llama3.2-3b

Uses the reduced (smoke) config of the chosen architecture so it runs on
CPU; the same code path lowers at full scale in the dry-run.
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.launch.dryrun import make_decode_step
from repro.models import lm


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    args = ap.parse_args()

    cfg = get_arch(args.arch).smoke()
    rng = jax.random.PRNGKey(0)
    params = lm.init_lm(cfg, rng)
    max_len = args.prompt_len + args.gen_len

    prompts = jax.random.randint(
        rng, (args.batch, args.prompt_len), 0, cfg.vocab
    )

    # prefill: replay the prompt through the cached decode path so the
    # cache is warm (families without parallel prefill-into-cache share it)
    cache = lm.init_cache(cfg, args.batch, max_len)
    decode = jax.jit(make_decode_step(cfg), donate_argnums=())
    t0 = time.perf_counter()
    tok = prompts[:, :1]
    for t in range(args.prompt_len):
        nxt, cache = decode(params, {"tokens": prompts[:, t:t+1], "cache": cache})
    t_prefill = time.perf_counter() - t0

    # decode loop (greedy)
    generated = [nxt]
    t0 = time.perf_counter()
    for _ in range(args.gen_len - 1):
        nxt, cache = decode(params, {"tokens": nxt[:, None], "cache": cache})
        generated.append(nxt)
    t_decode = time.perf_counter() - t0
    out = jnp.stack(generated, axis=1)

    toks_per_s = args.batch * (args.gen_len - 1) / max(t_decode, 1e-9)
    print(f"arch={args.arch} (reduced) batch={args.batch}")
    print(f"prompt processed in {t_prefill*1e3:.0f} ms")
    print(f"decoded {out.shape[1]} tokens/seq at {toks_per_s:.0f} tok/s")
    print("sample token ids:", out[0, :16].tolist())
    assert bool(jnp.all(out >= 0)) and bool(jnp.all(out < cfg.vocab))


if __name__ == "__main__":
    main()
