"""`elasticdl-tpu` CLI entrypoint.

Reference parity: elasticdl_client/main.py — verbs `train`, `evaluate`,
`predict`, `zoo init/build/push`. This module currently exposes the verb
surface and local-mode dispatch; Kubernetes submission lands with the
cluster client (see elasticdl_tpu/client/k8s.py when present).
"""

from __future__ import annotations

import time

_ENTERED = time.time()      # before the imports: `start.launch` without /proc

import sys  # noqa: E402
from typing import List, Optional  # noqa: E402

from elasticdl_tpu.common.config import JobConfig  # noqa: E402
from elasticdl_tpu.observability import tracing  # noqa: E402
from elasticdl_tpu.version import __version__  # noqa: E402

VERBS = ("train", "evaluate", "predict", "zoo", "version")


def main(argv: Optional[List[str]] = None) -> int:
    tracing.mark_entry(_ENTERED)
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__)
        print(f"usage: elasticdl-tpu {{{'|'.join(VERBS)}}} [flags]")
        return 0
    verb, rest = argv[0], argv[1:]
    if verb == "version":
        print(__version__)
        return 0
    if verb not in VERBS:
        print(f"unknown verb {verb!r}; expected one of {VERBS}", file=sys.stderr)
        return 2
    # Deferred import: the launcher pulls in jax; keep `--help` cheap.
    from elasticdl_tpu.client import api

    if verb == "zoo":
        return api.zoo(rest)
    cfg = JobConfig.from_argv(rest)
    if verb == "train":
        return api.train(cfg)
    if verb == "evaluate":
        return api.evaluate(cfg)
    if verb == "predict":
        return api.predict(cfg)
    return 2


if __name__ == "__main__":
    raise SystemExit(main())
