PY ?= python
export PYTHONPATH := src:$(PYTHONPATH)

.PHONY: test smoke engine-test bench docs-check deps

# Tier-1 verify (ROADMAP): docs lint + the full test suite, fail-fast.
test: docs-check
	$(PY) -m pytest -x -q

# Engine-focused subset (fast iteration on the serving path).
engine-test:
	$(PY) -m pytest -q tests/test_engine.py \
	    tests/test_engine_serving_compat.py tests/test_sharded_engine.py \
	    tests/test_serving.py tests/test_lm_sharded.py

# End-to-end smoke: quickstart with tiny settings (~1 min on CPU).
smoke:
	QUICKSTART_STEPS=30 QUICKSTART_EVAL=128 $(PY) examples/quickstart.py

# Paper-protocol benchmarks (quick budget).
bench:
	$(PY) -m benchmarks.run

# Lint docs/ + README: compile python snippets, validate intra-repo links.
docs-check:
	$(PY) tools/docs_check.py

deps:
	pip install -r requirements-test.txt
