# gordo-tpu developer targets (reference parity: the Makefile drives
# tests/lint/images).

PYTHON ?= python
IMAGE  ?= gordo-tpu
TAG    ?= latest

.PHONY: test test-fast test-slow lint bench install image docs clean

install:
	$(PYTHON) -m pip install -e . --no-build-isolation --no-deps

test:
	$(PYTHON) -m pytest tests/ -q

# marker-gated fast lane (CI's per-push gate; measured ~3 min)
test-fast:
	$(PYTHON) -m pytest tests/ -q -m "not slow"

test-slow:
	$(PYTHON) -m pytest tests/ -q -m slow

# stdlib AST linter (no flake8 in this image; CI also runs flake8)
lint:
	$(PYTHON) scripts/lint.py

bench:
	$(PYTHON) bench.py

image:
	docker build -t $(IMAGE):$(TAG) .

docs:
	PYTHONPATH=. JAX_PLATFORMS=cpu $(PYTHON) scripts/gen_api_docs.py
	@ls docs/*.md

clean:
	rm -rf build dist *.egg-info .pytest_cache
	find . -name __pycache__ -type d -exec rm -rf {} +
