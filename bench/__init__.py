"""Benchmark of the LANS pretraining path on TPU: see run.py."""
