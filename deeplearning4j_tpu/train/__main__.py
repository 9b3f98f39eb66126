"""Trainer CLI: ``python -m deeplearning4j_tpu.train``.

Reference parity: parallelism/main/ParallelWrapperMain.java (headless
training entry point driven by flags). Loads a model or configuration with
ModelGuesser semantics, trains on an .npz dataset or a built-in fetcher,
and writes a native checkpoint zip.

Examples::

    python -m deeplearning4j_tpu.train model_or_conf.json \
        --data train.npz --epochs 3 --batch-size 128 --output trained.zip
    python -m deeplearning4j_tpu.train lenet.json --dataset mnist --epochs 1
    python -m deeplearning4j_tpu.train conf.json --data d.npz --data-parallel
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m deeplearning4j_tpu.train",
        description="Train a model from a config JSON / model zip / Keras h5.")
    p.add_argument("model", help="configuration JSON, native/DL4J zip, or Keras h5")
    src = p.add_mutually_exclusive_group(required=True)
    src.add_argument("--data", help=".npz file with arrays x and y "
                     "(optional fmask/lmask)")
    src.add_argument("--dataset", choices=["mnist", "emnist", "iris", "cifar10"],
                     help="built-in dataset fetcher")
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--batch-size", type=int, default=128)
    p.add_argument("--output", default="model.zip", help="checkpoint zip path")
    p.add_argument("--data-parallel", action="store_true",
                   help="shard batches over all local devices (ParallelWrapper)")
    p.add_argument("--listener-frequency", type=int, default=10,
                   help="score print frequency (iterations)")
    p.add_argument("--evaluate", action="store_true",
                   help="run classification evaluation after training")
    return p


def _load_model(path: str):
    from deeplearning4j_tpu.nn.graph import ComputationGraph, ComputationGraphConfiguration
    from deeplearning4j_tpu.nn.model import MultiLayerConfiguration, MultiLayerNetwork
    from deeplearning4j_tpu.utils.guesser import load_any

    obj = load_any(path)
    if isinstance(obj, MultiLayerConfiguration):
        return MultiLayerNetwork(obj).init()
    if isinstance(obj, ComputationGraphConfiguration):
        return ComputationGraph(obj).init()
    return obj  # already a model


def _load_data(args):
    if args.data:
        d = np.load(args.data)
        if "x" not in d or "y" not in d:
            raise SystemExit(f"{args.data}: expected arrays 'x' and 'y', "
                             f"found {sorted(d.files)}")
        fmask = d["fmask"] if "fmask" in d else None
        lmask = d["lmask"] if "lmask" in d else None
        if lmask is not None:
            return (d["x"], d["y"], fmask, lmask)
        if fmask is not None:
            return (d["x"], d["y"], fmask)
        return (d["x"], d["y"])
    from deeplearning4j_tpu.datasets.fetchers import (
        CifarDataSetIterator, EmnistDataSetIterator, IrisDataSetIterator,
        MnistDataSetIterator)

    it = {"mnist": MnistDataSetIterator, "emnist": EmnistDataSetIterator,
          "iris": IrisDataSetIterator, "cifar10": CifarDataSetIterator}[
              args.dataset](batch_size=args.batch_size)
    return it


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    from deeplearning4j_tpu.train.listeners import ScoreIterationListener
    from deeplearning4j_tpu.utils.serialization import save_network

    model = _load_model(args.model)
    if not hasattr(model, "fit"):
        raise SystemExit(f"{args.model} does not contain a trainable model")
    model.set_listeners(ScoreIterationListener(args.listener_frequency))
    data = _load_data(args)

    if args.data_parallel:
        from deeplearning4j_tpu.parallel import ParallelWrapper

        ParallelWrapper(model).fit(data, epochs=args.epochs,
                                   batch_size=args.batch_size)
    else:
        model.fit(data, epochs=args.epochs, batch_size=args.batch_size)

    save_network(model, args.output)
    print(f"saved {args.output}")

    if args.evaluate:
        ev = model.evaluate(data, batch_size=args.batch_size)
        print(ev.stats() if hasattr(ev, "stats") else ev)
    return 0


if __name__ == "__main__":
    from deeplearning4j_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()   # process entry only: main() stays config-pure
    sys.exit(main())
