"""Test-only reference module: the base semantics, unchanged.  A run under
it must read exactly as a run under ``bench/lib/reference.py``."""
from bench.lib.reference import Reference as Base


class Reference(Base):
    pass
