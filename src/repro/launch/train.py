"""Training launcher.

Smoke (CPU):      PYTHONPATH=src python -m repro.launch.train --arch olmo-1b \
                      --smoke --steps 30
Production shape: same CLI on a TPU pod slice; --multi-pod switches the
mesh to (pod, data, model) with the pod axis data-parallel (default) or
pipelined (--pipeline, see distributed/pipeline.py).
"""

from __future__ import annotations

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config on local devices")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--quant-bits", type=int, default=16,
                    help="L-SPINE datapath: 2/4/8 = QAT fake-quant")
    ap.add_argument("--spiking-ffn", action="store_true",
                    help="L-SPINE spiking execution of FFN blocks")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--resume", action="store_true")
    from repro.launch.profiling import add_profile_flag, maybe_trace
    from repro.obs import add_metrics_flag, add_server_flag

    add_profile_flag(ap, "/tmp/repro_trace/train")
    add_metrics_flag(ap, "/tmp/repro_metrics/train.jsonl")
    add_server_flag(ap)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    import dataclasses

    from repro import obs
    from repro.configs import get_config
    from repro.configs.base import SpikingConfig
    from repro.quant.formats import PrecisionConfig
    from repro.train import optimizer as opt
    from repro.train.trainer import TrainConfig, Trainer

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.quant_bits != 16:
        cfg = dataclasses.replace(
            cfg, precision=PrecisionConfig(bits=args.quant_bits,
                                           group_size=-1))
    if args.spiking_ffn:
        cfg = dataclasses.replace(cfg, spiking=SpikingConfig())

    tcfg = TrainConfig(
        steps=args.steps, batch=args.batch, seq=args.seq,
        ckpt_dir=args.ckpt_dir,
        opt=opt.OptConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 5),
                          total_steps=args.steps),
    )
    if not args.resume:
        import shutil
        shutil.rmtree(args.ckpt_dir, ignore_errors=True)
    # enable BEFORE constructing the trainer — instruments bind at
    # construction time (no-op handles otherwise).  --metrics-port
    # implies the registry (a scrape of a disabled registry is empty).
    metrics_on = bool(args.metrics or args.metrics_port is not None)
    registry = obs.enable_default() if metrics_on else None
    server = None
    if args.metrics_port is not None:
        server = obs.ObsServer(registry, port=args.metrics_port)
        port = server.start()
        print(f"[obs] serving http://127.0.0.1:{port}/metrics "
              f"(/healthz, /spans?since=N)")
    trainer = Trainer(cfg, tcfg)
    with maybe_trace(args.profile):
        out = trainer.run()
    print(f"first loss {out['first_loss']:.4f} -> "
          f"final loss {out['final_loss']:.4f}")
    if args.metrics:
        path = obs.write_jsonl(registry, args.metrics,
                               meta={"entry": "train", "arch": args.arch,
                                     "steps": args.steps})
        print(f"[obs] metrics written to {path} — validate with "
              f"`python -m repro.obs.validate {path}`")
    if server is not None:
        server.stop()


if __name__ == "__main__":
    main()
