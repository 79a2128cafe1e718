"""The chip benchmark of the ES-dLLM serving path (see bench/run.py)."""
