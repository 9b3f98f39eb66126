"""``python -m deeplearning4j_tpu.serve name=path [name=path ...]``

Stand up the inference server: import each model (Keras ``.h5`` or DL4J
``.zip``, format auto-detected), run the AOT warm pipeline (restoring /
writing ``<path>.aotbundle`` sidecars where ``DL4J_TPU_AOT_BUNDLE=1``), and
serve them all from one port. The socket binds only after every model is
warm — time-to-first-request never pays an XLA compile.

Options: ``--port N`` (default 8000; 0 = OS-assigned, printed on stdout).
"""

from __future__ import annotations

import argparse
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m deeplearning4j_tpu.serve",
        description="continuous-batching inference server")
    ap.add_argument("models", nargs="+", metavar="name=path",
                    help="model to serve: name=path/to/model.h5|.zip")
    ap.add_argument("--port", type=int, default=8000)
    args = ap.parse_args(argv)

    from deeplearning4j_tpu.serve import InferenceServer, ModelRegistry

    registry = ModelRegistry()
    for spec in args.models:
        name, _, path = spec.partition("=")
        if not path:
            ap.error(f"expected name=path, got {spec!r}")
        print(f"loading {name} from {path} ...", flush=True)
        registry.load(name, path)
    srv = InferenceServer(registry).start(port=args.port)
    print(f"serving {', '.join(registry.names())} on "
          f"http://127.0.0.1:{srv.port}", flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        srv.stop()
    return 0


if __name__ == "__main__":
    from deeplearning4j_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()   # process entry only: main() stays config-pure
    sys.exit(main())
