#!/usr/bin/env python
"""graft-lint CLI: project-specific static analysis.

Usage::

    python tools/lint.py [paths...]          # default: mxnet_tpu tools
    python tools/lint.py --list-rules
    python tools/lint.py --rule env-knob mxnet_tpu

Exit status 1 when any violation is reported (``make lint`` / the
ci.yaml ``lint`` stage).  Rule catalog and suppression syntax:
docs/architecture/static_analysis.md.

The analysis package is loaded standalone (stdlib-only modules, no
``import mxnet_tpu``), so linting never pays the jax import and runs on
machines without the accelerator stack.
"""
import importlib.util
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PKG_DIR = os.path.join(_ROOT, "mxnet_tpu", "analysis")


def _load_analysis():
    """Import mxnet_tpu/analysis under the alias ``graft_analysis`` so
    its relative imports resolve without importing mxnet_tpu itself."""
    spec = importlib.util.spec_from_file_location(
        "graft_analysis", os.path.join(_PKG_DIR, "__init__.py"),
        submodule_search_locations=[_PKG_DIR])
    pkg = importlib.util.module_from_spec(spec)
    sys.modules["graft_analysis"] = pkg
    spec.loader.exec_module(pkg)
    import importlib as _il
    return _il.import_module("graft_analysis.graft_lint")


def main(argv=None):
    graft_lint = _load_analysis()
    argv = sys.argv[1:] if argv is None else argv
    if "--root" not in argv:
        argv = ["--root", _ROOT] + list(argv)
    return graft_lint.main(argv)


if __name__ == "__main__":
    sys.exit(main())
