"""Test-only reference module: the base semantics with a tighter filter,
which refuses every node whose index is divisible by 5.  The program does
not refuse them, so the check must find its filtering wrong."""
from bench.lib.reference import Reference as Base


class Reference(Base):
    def feasible(self, cols, pod):
        ok = super().feasible(cols, pod)
        ok[::5] = False
        return ok
