/*!
 * C predict ABI — the standalone minimal inference surface for language
 * bindings and embedded deployment.
 *
 * Reference: include/mxnet/c_predict_api.h + src/c_api/c_predict_api.cc
 * (SURVEY §3.4): load symbol JSON + params blob, bind, set input, forward,
 * read output — the ABI the matlab binding and the amalgamation mobile
 * builds sit on.  Signatures mirror the reference's (float I/O, uint32
 * shape indptr encoding).
 *
 * Implementation note (the explicit ABI stance):
 * the compute path of this framework is XLA driven through the Python
 * package, so libmxnet_tpu_predict embeds the CPython interpreter — the
 * same one-runtime/N-frontends shape as the reference where every binding
 * rides libmxnet.so.  Callers link: `python3-config --includes --embed
 * --ldflags` + this library (built from src/predict_capi.cc).
 *
 * All functions return 0 on success, -1 on error; MXGetLastError() gives
 * the message.  Handles are opaque.
 */
#ifndef MXNET_TPU_C_PREDICT_API_H_
#define MXNET_TPU_C_PREDICT_API_H_

#include <stddef.h>
#include <stdint.h>

#ifdef __cplusplus
extern "C" {
#endif

typedef void* PredictorHandle;

/*! \brief last error message of the calling thread. */
const char* MXGetLastError(void);

/*!
 * \brief create a predictor from a symbol JSON string and a params blob
 *  (the dmlc .params format written by save_checkpoint).
 * \param symbol_json_str   null-terminated symbol JSON
 * \param param_bytes       pointer to the params blob
 * \param param_size        blob size in bytes
 * \param dev_type          1 = cpu, 4 = tpu (2/gpu aliases the accelerator)
 * \param dev_id            device ordinal
 * \param num_input_nodes   number of input names
 * \param input_keys        input names (e.g. {"data"})
 * \param input_shape_indptr CSR-style offsets into input_shape_data,
 *                           length num_input_nodes + 1
 * \param input_shape_data  concatenated input shapes (uint32 dims)
 * \param out               the created handle
 */
int MXPredCreate(const char* symbol_json_str, const void* param_bytes,
                 int param_size, int dev_type, int dev_id,
                 uint32_t num_input_nodes, const char** input_keys,
                 const uint32_t* input_shape_indptr,
                 const uint32_t* input_shape_data, PredictorHandle* out);

/*! \brief copy float data into the named input. */
int MXPredSetInput(PredictorHandle handle, const char* key,
                   const float* data, uint32_t size);

/*! \brief run the forward pass. */
int MXPredForward(PredictorHandle handle);

/*! \brief shape of output `index`: *shape_data points at an internal
 *  buffer valid until the next call on this handle. */
int MXPredGetOutputShape(PredictorHandle handle, uint32_t index,
                         uint32_t** shape_data, uint32_t* shape_ndim);

/*! \brief copy output `index` into data (float, `size` elements). */
int MXPredGetOutput(PredictorHandle handle, uint32_t index, float* data,
                    uint32_t size);

/*! \brief rebind the predictor for new input shapes (same encoding as
 *  MXPredCreate). */
int MXPredReshape(PredictorHandle handle, uint32_t num_input_nodes,
                  const char** input_keys,
                  const uint32_t* input_shape_indptr,
                  const uint32_t* input_shape_data);

/*! \brief free the predictor. */
int MXPredFree(PredictorHandle handle);

#ifdef __cplusplus
}  /* extern "C" */
#endif

#endif  /* MXNET_TPU_C_PREDICT_API_H_ */
