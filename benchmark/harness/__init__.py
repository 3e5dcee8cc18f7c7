"""The benchmark's harness: generic code that no cell, traffic kind or layer
metric needs an edit of. See benchmark/README.md."""
