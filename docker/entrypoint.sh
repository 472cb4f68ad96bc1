#!/bin/sh
# serve  -> cluster-serving stack (broker + engine + HTTP frontend)
# anything else -> exec verbatim (python train.py, pytest, a shell, ...)
set -e
case "$1" in
  serve)
    shift
    exec python -m analytics_zoo_tpu.serving.stack --host 0.0.0.0 "$@"
    ;;
  *)
    exec "$@"
    ;;
esac
