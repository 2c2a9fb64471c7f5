"""Test sizes of the configurations ``bench/tests/tiny.py`` does not name:
each is cut to the same few thousand users as the first."""
from bench.tests import tiny

tiny.SIZES.setdefault("g500-s23", {"scale": 11})
