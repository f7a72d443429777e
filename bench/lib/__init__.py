"""The benchmark's yardstick: cluster and traffic generation from the seed,
the plain reference, trace reduction, work counts and metric arithmetic.

Nothing here imports the program under test except ``serve.py``, which
drives it; the base reference (``reference.py``) and every configuration's
own reference module import nothing of it."""
