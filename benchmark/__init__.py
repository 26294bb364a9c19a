"""The benchmark: one command runs one cell once (``python3 -m benchmark.run``).

Everything that decides a number lives in this directory so that a PR which
claims a gain cannot change the yardstick. See ``README.md``.
"""
