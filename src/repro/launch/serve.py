"""Serving launcher: continuous-batching engine on synthetic prompts."""

from __future__ import annotations

import argparse


def main():
    from repro.configs import add_geometry_flags

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    add_geometry_flags(ap)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--quant-bits", type=int, default=16)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    import dataclasses

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models.api import get_model
    from repro.quant.formats import PrecisionConfig
    from repro.serve.engine import EngineConfig, Request, ServeEngine

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.quant_bits != 16:
        cfg = dataclasses.replace(
            cfg, precision=PrecisionConfig(bits=args.quant_bits,
                                           group_size=-1))
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, EngineConfig(slots=args.slots,
                                                max_len=256))
    rng = np.random.default_rng(0)
    for uid in range(args.requests):
        plen = int(rng.integers(4, 24))
        eng.add_request(Request(
            uid=uid,
            prompt=rng.integers(0, cfg.vocab, plen).astype(np.int32),
            max_new_tokens=args.max_new,
        ))
    stats = eng.run_until_done()
    print(stats)


if __name__ == "__main__":
    main()
