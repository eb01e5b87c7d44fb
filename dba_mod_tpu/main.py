"""CLI — reference-compatible entry point.

    python -m dba_mod_tpu.main --params configs/cifar_params.yaml

mirrors `python main.py --params utils/cifar_params.yaml` (reference
main.py:88-92); it also accepts the reference's own YAML files unchanged.
Subcommands beyond the reference:

    pretrain   train a clean model and save the checkpoint that attack
               configs resume from (replaces the reference's Google-Drive
               pretrained artifacts, README.md:33-34)
    fetch      dataset preflight: exact upstream URLs + sha256 checksums
               for CIFAR/MNIST/Tiny-ImageNet/LOAN, download + verify (or
               --check-only), with an explicit printout of the synthetic
               fallback any absent dataset will engage
    cache-tiny decode the Tiny-ImageNet image folders once into an .npz
               cache for fast loading
    loan-etl / tiny-etl   the reference's offline data prep
               (utils/loan_preprocess.py, utils/tinyimagenet_reformat.py)
    report     render a run folder's defense-forensics stream
               (forensics.jsonl, written when `forensics: true`) into a
               standalone HTML round-audit
"""
from __future__ import annotations

import argparse
import logging
import os
import sys
from pathlib import Path

from dba_mod_tpu.config import Params


def _train(args) -> int:
    from dba_mod_tpu.fl.experiment import Experiment
    from dba_mod_tpu.utils import run_guard
    from dba_mod_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    params = Params.from_yaml(args.params)
    if args.epochs is not None:
        params.raw["epochs"] = args.epochs
    if args.synthetic:
        params.raw["synthetic_data"] = True
    if args.resume:
        if args.resume == "auto":
            # discover + continue the newest verified checkpoint under
            # run_dir (README "Crash & preemption tolerance"). Same guard
            # as config.py's validation — the CLI override lands after
            # from_yaml, so re-check the combination it would reject
            if not bool(params.raw.get("checkpoint_manifests", True)):
                raise SystemExit(
                    "--resume auto requires checkpoint_manifests: true "
                    "(auto-resume only restores manifest-verified "
                    "checkpoints; with manifests off every relaunch "
                    "would silently start a fresh run)")
            params.raw["resumed_model"] = "auto"
        else:
            params.raw.update(resumed_model=True,
                              resumed_model_name=args.resume)
    from dba_mod_tpu.parallel.distributed import PeerLostError
    exp = Experiment(params, save_results=not args.no_save)
    try:
        last = exp.run()
    except PeerLostError as e:
        # elastic verdict (README "Elastic multi-host"): a peer host is
        # gone. The run's finally already flushed checkpoints/recorder;
        # exit with the distinct code so the supervisor relaunches the
        # SURVIVORS with JAX_NUM_PROCESSES shrunk + --resume auto.
        # os._exit: the jax.distributed atexit teardown would block on the
        # dead peer — nothing left to flush is worth that hang.
        print(f"peer lost: {e} — relaunch the survivors with "
              f"JAX_NUM_PROCESSES shrunk and --resume auto", flush=True)
        sys.stdout.flush()
        sys.stderr.flush()
        logging.shutdown()
        os._exit(run_guard.EXIT_PEER_LOST)
    if exp.interrupted:
        # graceful SIGTERM/SIGINT stop: distinct exit code so run wrappers
        # know to relaunch with --resume auto rather than report failure
        done = last.get("epoch") if last else exp.start_epoch - 1
        print(f"interrupted: graceful stop after epoch {done} — "
              f"resume with --resume auto")
        return run_guard.EXIT_INTERRUPTED
    if not last:  # resume checkpoint already at/after the final epoch
        print(f"no rounds to run: start_epoch={exp.start_epoch} > "
              f"epochs={params['epochs']}")
        return 0
    print(f"final: epoch={last.get('epoch')} "
          f"acc={last.get('global_acc'):.2f} "
          f"backdoor={last.get('backdoor_acc')}")
    return 0


def _pretrain(args) -> int:
    from dba_mod_tpu import checkpoint as ckpt
    from dba_mod_tpu.fl.experiment import Experiment
    from dba_mod_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    params = Params.from_yaml(args.params)
    params.raw.update(is_poison=False, resumed_model=False,
                      save_model=False)
    if args.epochs is not None:
        params.raw["epochs"] = args.epochs
    if args.synthetic:
        params.raw["synthetic_data"] = True
    exp = Experiment(params, save_results=False)
    last = exp.run()
    out = Path(str(params.get("checkpoint_dir", "saved_models"))) / (
        args.out or f"{params.type}_pretrain/model_last.pt.tar.epoch_"
                    f"{params['epochs']}")
    ckpt.save_checkpoint(out, exp.global_vars, int(params["epochs"]),
                         float(params["lr"]))
    acc = last.get("global_acc")
    print(f"pretrained to epoch {params['epochs']} "
          f"acc={acc if acc is None else round(acc, 2)} -> {out}")
    return 0


def _fetch(args) -> int:
    from dba_mod_tpu.data.fetch import run_preflight
    data_dir = args.data_dir
    types = [args.type] if args.type and args.type != "all" else None
    if args.params:
        params = Params.from_yaml(args.params)
        types = [params.type]
        if args.data_dir == "./data":  # YAML wins unless overridden
            data_dir = str(params.get("data_dir", "./data"))
    return run_preflight(types, data_dir, check_only=args.check_only)


def _cache_tiny(args) -> int:
    import numpy as np
    from dba_mod_tpu.data.datasets import load_tiny_imagenet
    data = load_tiny_imagenet(args.data_dir)
    if data is None:
        print("tiny-imagenet-200 folders not found (or PIL missing)",
              file=sys.stderr)
        return 1
    out = Path(args.data_dir) / "tiny-imagenet-200.npz"
    np.savez_compressed(out, train_x=data.train_images,
                        train_y=data.train_labels, test_x=data.test_images,
                        test_y=data.test_labels)
    print(f"cached {len(data.train_labels)} train / "
          f"{len(data.test_labels)} val images -> {out}")
    return 0


def _loan_etl(args) -> int:
    from dba_mod_tpu.data.etl import preprocess_loan
    n = preprocess_loan(args.input, Path(args.data_dir) / "loan")
    print(f"wrote {n} per-state loan CSVs")
    return 0


def _tiny_etl(args) -> int:
    from dba_mod_tpu.data.etl import reformat_tiny_imagenet_val
    n = reformat_tiny_imagenet_val(Path(args.data_dir) / "tiny-imagenet-200")
    print(f"moved {n} val images into per-class folders")
    return 0


def _report(args) -> int:
    from dba_mod_tpu.utils.forensics import write_report
    out = write_report(Path(args.run),
                       Path(args.out) if args.out else None)
    print(f"wrote {out}")
    return 0


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(prog="dba_mod_tpu", description=__doc__)
    sub = parser.add_subparsers(dest="cmd")

    def common(p):
        p.add_argument("--params", required=True,
                       help="YAML config (reference schema)")
        p.add_argument("--epochs", type=int, default=None)
        p.add_argument("--synthetic", action="store_true",
                       help="force the synthetic dataset backend")

    train = sub.add_parser("train", help="run an FL experiment (default)")
    common(train)
    train.add_argument("--no-save", action="store_true")
    train.add_argument(
        "--resume", default=None, metavar="auto|NAME",
        help="'auto': discover the newest verified checkpoint under "
             "run_dir, reuse that run folder and continue its recorder "
             "stream; any other value resumes checkpoint_dir/NAME "
             "(overrides the YAML's resumed_model keys)")
    pre = sub.add_parser("pretrain", help="train+save a clean model")
    common(pre)
    pre.add_argument("--out", default=None,
                     help="checkpoint path under saved_models/")
    fe = sub.add_parser(
        "fetch", help="dataset preflight: check/download + sha256-verify "
                      "the real datasets; absent ones fall back to the "
                      "deterministic synthetic backend at run time")
    fe.add_argument("--params", default=None,
                    help="YAML config: preflight exactly the dataset this "
                         "experiment needs (type + data_dir)")
    fe.add_argument("--type", default="all",
                    choices=["all", "cifar", "mnist", "tiny-imagenet-200",
                             "loan"])
    fe.add_argument("--data-dir", default="./data")
    fe.add_argument("--check-only", action="store_true",
                    help="no network: report presence/integrity and the "
                         "synthetic-fallback consequences, exit nonzero "
                         "if anything is missing")
    ct = sub.add_parser("cache-tiny")
    ct.add_argument("--data-dir", default="./data")
    le = sub.add_parser("loan-etl")
    le.add_argument("--input", required=True, help="raw lending-club CSV")
    le.add_argument("--data-dir", default="./data")
    te = sub.add_parser("tiny-etl")
    te.add_argument("--data-dir", default="./data")
    rp = sub.add_parser(
        "report", help="render forensics.jsonl into a standalone HTML "
                       "round-audit (requires a run with forensics: true)")
    rp.add_argument("--run", required=True,
                    help="run folder containing forensics.jsonl")
    rp.add_argument("--out", default=None,
                    help="output path (default: RUN/forensics_report.html)")
    return parser


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    known = {"train", "pretrain", "fetch", "cache-tiny", "loan-etl",
             "tiny-etl", "report"}
    if argv and argv[0] not in known:
        argv = ["train"] + argv  # reference style: --params only
    args = build_parser().parse_args(argv)
    return {"train": _train, "pretrain": _pretrain, "fetch": _fetch,
            "cache-tiny": _cache_tiny, "loan-etl": _loan_etl,
            "tiny-etl": _tiny_etl, "report": _report}[args.cmd](args)


if __name__ == "__main__":
    sys.exit(main())
