"""The on-chip benchmark of the cluster-pruned search (see ``run.py``)."""
