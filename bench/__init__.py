"""On-chip benchmark of the ODE-adjoint training paths (``bench/run.py``)."""
