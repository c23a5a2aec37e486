"""The program's native host library (``csrc/libbigdl_host.so``, gitignored):
built once per checkout, loaded afterwards, and never silently absent — with
the numpy fall-back the host path is a different program."""

from __future__ import annotations


def ensure(required: bool) -> bool:
    from bigdl_tpu import native

    if not native.available():
        native.build()
    ok = native.available()
    if required and not ok:
        raise SystemExit("benchmark: the host library (csrc/) did not build")
    return ok
