"""Model modules, one file per model that a configuration can name.

A configuration file names its model by ``"model": "<name>"`` (where it
names none, ``dlrm_dot``). The harness loads ``bench/models/<name>.py`` by
path, as it loads a metric's reader, and takes from it everything that
depends on the model's shape; ``dlrm_dot.py`` documents the functions such a
file provides. Adding a model adds a file here and edits none.
"""
