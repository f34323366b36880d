"""repro — FlashMLA-ETAP reproduction package.

Importing the package imports nothing else: the pure-stdlib subpackages
(``repro.analysis`` — the CI lint job runs it in a ruff-only environment
with no JAX wheel) stay importable without JAX.
"""
