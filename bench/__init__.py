"""The chip benchmark: cells named in ``BENCHMARK.json``, run one at a time
by ``bench/run.py``. Everything that decides a number lives here, apart from
the program under test."""
