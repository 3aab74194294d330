# Convenience targets; CI and the driver call `make test`.
PY ?= python

.PHONY: test native dryrun

native:
	$(PY) -m sagecal_tpu.io.native --build

test:
	$(PY) -m pytest tests/ -q

dryrun:
	$(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"
