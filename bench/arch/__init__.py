"""The benchmark's side of each architecture, one module per
``architecture`` named in the configuration files: how its weights are
laid out, made and sharded, how the program takes them, and where its
protected GEMMs are.  ``bench/spec.py`` says what a module provides."""
