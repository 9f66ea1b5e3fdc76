"""Shared CLI bootstrap: stub the `paddle_tpu` package namespace.

The stdlib-only analyzers (tracelint's AST pass, racelint) must import
`paddle_tpu.analysis` WITHOUT executing the real paddle_tpu/__init__.py
(which imports jax) — the gates have to stay fast enough to run on
every CI invocation, and a lint has no business taking the chip.
Installing a bare package module with the right ``__path__``
lets submodule imports resolve normally.  No-op when paddle_tpu is
already imported (e.g. under pytest).
"""
from __future__ import annotations

import os
import sys
import types


def light_paddle_tpu(repo):
    """Make `paddle_tpu.*` submodules importable jax-free."""
    if "paddle_tpu" not in sys.modules:
        pkg = types.ModuleType("paddle_tpu")
        pkg.__path__ = [os.path.join(repo, "paddle_tpu")]
        sys.modules["paddle_tpu"] = pkg
