"""CI tooling package — makes ``python -m ci.graftlint`` runnable from
the repo root and the ``ci/check_*.py`` scripts importable as modules
(``ci.check_compile_cache``) for graftlint's orchestrated pass."""
