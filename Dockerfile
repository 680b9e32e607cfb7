# TPU image (reference Dockerfile builds on the paddle-gpu base; here the
# jax TPU wheel rides on a slim python base — run on a TPU VM).
FROM python:3.12-slim

RUN apt-get update && apt-get install -y --no-install-recommends \
        build-essential make git && \
    rm -rf /var/lib/apt/lists/*

WORKDIR /workspace/fleetx-tpu
COPY requirements.txt setup.py ./
RUN pip install --no-cache-dir "jax[tpu]==0.9.0" \
        -f https://storage.googleapis.com/jax-releases/libtpu_releases.html && \
    pip install --no-cache-dir -r requirements.txt

COPY fleetx_tpu ./fleetx_tpu
COPY tools ./tools
COPY tasks ./tasks
COPY projects ./projects
RUN pip install --no-cache-dir -e . && \
    make -C fleetx_tpu/data/native

CMD ["python", "tools/train.py", "-c", \
     "fleetx_tpu/configs/nlp/gpt/pretrain_gpt_345M_synthetic.yaml"]
