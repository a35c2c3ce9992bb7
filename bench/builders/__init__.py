"""Builders of the system under test, one module per ``build.builder``
name of a configuration. Each exposes ``build(cfg, rows, seed) ->
System``."""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional


@dataclasses.dataclass
class System:
    """What a loop drives: the resident engine (``join_batch``) and, for
    a system that serves requests, a factory of fresh schedulers over
    it."""

    engine: Any
    scheduler: Optional[Callable[[], Any]] = None
