"""moe_shared_w1280_ms: what it reads is in ``moe_shared_w1280_ms.json``; the reduction is
``benchmark/scope_parts.py``."""

from benchmark import scope_parts

read = scope_parts.reader(__file__)
