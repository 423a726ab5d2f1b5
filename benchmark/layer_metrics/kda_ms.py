"""kda_ms: what it reads is in ``kda_ms.json``; the reduction is
``benchmark/scope_parts.py``."""

from benchmark import scope_parts

read = scope_parts.reader(__file__)
