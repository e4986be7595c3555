"""Architecture modules: one file for each model family a configuration
file can name under ``"arch"`` (the contract is in ``qwen3_moe``'s
docstring and in PERF.md, section 3)."""
