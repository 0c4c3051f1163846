"""Serving entry point: decode-serving and sweep-serving behind one CLI.

Two modes, dispatched on the first argument:

* ``decode`` — the batched LLM serving driver: prefill + greedy decode
  loop with KV cache over synthetic prompts; reports tokens/s and
  validates the cache path end to end.

      PYTHONPATH=src python -m repro.launch.serve decode \
          --arch gemma3-12b --smoke --batch 4 --prompt-len 64 --gen 32

* ``sweep`` — the persistent sweep server
  (:mod:`repro.launch.sweep_serve`): accepts streaming (workload, arch,
  density, method, budget) queries over a local socket, coalesces
  same-signature queries into shared mega-batch rounds, streams
  best-so-far results, checkpoints populations and survives crashes.

      PYTHONPATH=src python -m repro.launch.serve sweep \
          --port 7333 --checkpoint-dir /tmp/sweeps

Bare legacy flags (no mode word) keep selecting decode — existing
scripts and tests predate the sweep mode.
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

MODES = ("decode", "sweep")

_USAGE = """\
usage: python -m repro.launch.serve <mode> [mode options]

modes:
  decode   batched LLM serving driver (prefill + greedy decode loop);
           options: --arch --smoke --batch --prompt-len --gen
  sweep    persistent accelerator-search sweep server (query coalescing,
           checkpointed populations, crash recovery); options: --host
           --port --checkpoint-dir --checkpoint-every --max-restarts
           --no-warm-start --device-rounds

`<mode> --help` shows that mode's full options.  Legacy invocations with
bare flags (no mode word) run decode.
"""


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] in MODES:
        if argv[0] == "sweep":
            from . import sweep_serve
            return sweep_serve.main(argv[1:])
        return decode_main(argv[1:])
    if argv[:1] in (["-h"], ["--help"]):
        print(_USAGE)
        return 0
    return decode_main(argv)        # legacy: bare flags mean decode


def decode_main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.serve decode",
        description="Batched serving driver: prefill + greedy decode "
                    "loop with KV cache over synthetic prompts; reports "
                    "tokens/s.")
    ap.add_argument("--arch", default="gemma3-12b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, smoke_config
    from repro.models.model import Model

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    b, pl_, g = args.batch, args.prompt_len, args.gen
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, pl_)),
                          jnp.int32)

    max_len = pl_ + g + 1
    if cfg.n_enc_layers:
        enc = jnp.asarray(rng.standard_normal((b, pl_, cfg.d_model)),
                          jnp.bfloat16) * 0.02
        cache = model.init_cache(b, max_len, params=params,
                                 enc_embeds=enc)
    else:
        cache = model.init_cache(b, max_len)

    decode = jax.jit(model.decode_step, donate_argnums=(1,))

    # prefill by stepping the prompt through decode (correct though not
    # the fast path; prefill_32k lowers the batched forward instead)
    t0 = time.time()
    tok = prompts[:, 0:1]
    for i in range(pl_):
        logits, cache = decode(params, cache, tok, jnp.int32(i))
        tok = prompts[:, i + 1:i + 2] if i + 1 < pl_ else \
            jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    prefill_s = time.time() - t0

    t0 = time.time()
    out_tokens = []
    for i in range(g):
        logits, cache = decode(params, cache, tok, jnp.int32(pl_ + i))
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        out_tokens.append(np.asarray(tok)[:, 0])
    decode_s = time.time() - t0
    gen = np.stack(out_tokens, axis=1)

    print(f"arch={cfg.name} batch={b} prompt={pl_} gen={g}")
    print(f"prefill: {pl_ * b / max(prefill_s, 1e-9):.1f} tok/s   "
          f"decode: {g * b / max(decode_s, 1e-9):.1f} tok/s")
    print(f"first generated rows: {gen[:2, :8].tolist()}")
    assert gen.shape == (b, g)
    assert (gen >= 0).all() and (gen < cfg.vocab_size).all()
    print("serve ok")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
