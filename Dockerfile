# Counterpart of pytorch_impl/Dockerfile: the reference image warm-builds
# the native modules and runs the demo once ("build success => tests pass",
# .github/workflows/build.yml:12-45 + Dockerfile:12). This image instead
# installs the package, JIT-builds the C++ runtime, and runs the real test
# suite on a virtual 8-device CPU mesh — the fake-backend the reference
# lacked (SURVEY §4).
FROM python:3.12-slim

RUN apt-get update && apt-get install -y --no-install-recommends \
        g++ make \
    && rm -rf /var/lib/apt/lists/*

WORKDIR /app
COPY pyproject.toml README.md ./
COPY garfield_tpu ./garfield_tpu
COPY tests ./tests
COPY chip_smoke.py __graft_entry__.py BASELINE.json ./

RUN pip install --no-cache-dir "jax[cpu]" flax optax orbax-checkpoint \
        chex einops pytest && \
    pip install --no-cache-dir -e .

# Warm-build the native C++ GAR kernels + multibuffer (import triggers the
# content-hashed g++ JIT build, native/__init__.py) and run the suite.
RUN python -c "import garfield_tpu.native as n; print('native:', n.available())" && \
    python -m pytest tests/ -q

# Default command: the browser demo (LEARN on Pima), like the reference's
# deployed demonstrator (LEARN/demo.py + scripts/deploy.sh).
EXPOSE 8000
CMD ["python", "-m", "garfield_tpu.apps.demo", "--port", "8000"]
