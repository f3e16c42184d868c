"""Input generators, driven by the run's ``--seed``."""
