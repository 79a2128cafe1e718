# Analytic cost model (costmodel.py) and the dry-run roofline reporter
# (roofline.py): PYTHONPATH=src python -m benchmarks.roofline
