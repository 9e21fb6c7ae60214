"""On-chip benchmark of the RNN tagger serving path; see ``run.py``."""
