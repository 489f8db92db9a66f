# Developer entry points. The tier-1 test command of record lives in
# ROADMAP.md; these targets wrap the static-analysis layer
# (docs/static_analysis.md).

PYTHON ?= python
# Diff base for lint-fast: any git ref (branch, SHA, HEAD~1, ...).
SINCE ?= HEAD

.PHONY: lint lint-fast lint-rules serve chaos chaos-serve

# Chaos soak, short seeded schedule (CI-sized): drive the 4-process
# elastic CPU fault world through one seeded kill/hang + the serving-side
# probe and assert the end-state invariants (docs/fault_tolerance.md
# "Elastic multihost"). The long soak is `pytest -m slow
# tests/test_elastic_multihost.py`.
chaos:
	$(PYTHON) -m tools.chaos --seed 1 --faults 1 --steps 8 --ckpt-every 3

# Serving-plane survivability soak (docs/serving.md "Survivability"):
# two tiny identical-weight gen servers behind the real gateway, driven
# through backend death mid-stream (token-exact resume), a pre-first-chunk
# wedge (hedge wins), a deadline storm (in-queue shed, full refund), and
# a brownout ladder walk — then asserts nothing leaked and arealint is
# still clean.
chaos-serve:
	$(PYTHON) -m tools.chaos --serve

# Local serving stack (docs/serving.md): one generation engine + gen
# server + the OpenAI-compatible gateway in a single process. Pass a
# checkpoint with ARGS="--model-path /path/to/hf_ckpt --port 8000";
# without one it serves a tiny random-weight model (smoke-test mode).
serve:
	$(PYTHON) -m areal_tpu.gateway $(ARGS)

# Full whole-program scan: areal_tpu/ tools/ tests/, project rules on
# (incl. the v4 resource-lifecycle typestate family), baseline applied.
# This is what tier-1's TestFullTreeGate enforces. `make lint-rules`
# lists the full catalog, lifecycle rules included — rule modules
# register themselves through tools/arealint/__init__.py.
lint:
	$(PYTHON) -m tools.arealint

# Pre-commit fast path (<2 s on a small diff): scan only the Python
# files touched vs $(SINCE), PLUS untracked files — `git diff` alone
# never lists a brand-new module, which is exactly where a fresh
# PartitionSpec typo would live. git runs OUT HERE — the linter itself
# is pure stdlib and reads the file list from stdin (--changed-only).
# Cross-module context degrades to the changed set by design: the scan
# is exactly a full scan restricted to those files (property pinned by
# tests/test_arealint_spmd.py).
# The ref is verified first: a typo'd $(SINCE) must fail loudly, not
# let the pipeline swallow git's error and report a false "clean".
lint-fast:
	@git rev-parse --verify --quiet "$(SINCE)^{commit}" >/dev/null || \
		{ echo "lint-fast: unknown ref '$(SINCE)'" >&2; exit 2; }
	{ git diff --name-only $(SINCE); \
	  git ls-files --others --exclude-standard; } | \
		$(PYTHON) -m tools.arealint --changed-only --since $(SINCE)

lint-rules:
	$(PYTHON) -m tools.arealint --list-rules
