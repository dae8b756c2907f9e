# Sphinx configuration for the libskylark_tpu documentation site
# (the analog of the reference's doc/sphinx tree). Build with:
#   sphinx-build -b html docs docs/_build
# The dev image ships no sphinx; CI environments that have it can add the
# build to script/ci.
import os
import sys

sys.path.insert(0, os.path.abspath(".."))

project = "libskylark_tpu"
author = "libskylark_tpu developers"
release = "0.4"

extensions = [
    "sphinx.ext.autodoc",
    "sphinx.ext.napoleon",
    "sphinx.ext.mathjax",
    "sphinx.ext.viewcode",
]

autodoc_mock_imports = ["jax", "jaxlib", "orbax", "scipy", "h5py"]
exclude_patterns = ["_build"]
html_theme = "alabaster"
