# GENERATED by `python -m mmlspark_tpu.core.apigen` — do not edit.
# One constructor per mmlspark-tpu pipeline stage, via reticulate.

#' @keywords internal
mt_stage <- function(module, cls, ...) {
  mod <- reticulate::import(module, delay_load = TRUE)
  mod[[cls]](...)
}

#' BestModel (Model)
#'
#' A fitted Transformer produced by an Estimator.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{allModelMetrics}: (uid, metric) per candidate (default None)
#'   \item \code{bestModel}: Winning transformer (default None)
#'   \item \code{bestModelMetrics}: Winning metric value (default nan)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_best_model <- function(...) {
  mt_stage("mmlspark_tpu.automl.tune", "BestModel", ...)
}

#' FindBestModel (Estimator)
#'
#' Evaluates already-fitted models on a dataset, keeps the best (``FindBestModel.scala:55-130``).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{evaluationMetric}: Metric name (default 'accuracy')
#'   \item \code{labelCol}: The name of the label column (default 'label')
#'   \item \code{models}: Fitted models (Transformers) to evaluate
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_find_best_model <- function(...) {
  mt_stage("mmlspark_tpu.automl.tune", "FindBestModel", ...)
}

#' TuneHyperparameters (Estimator)
#'
#' Randomized hyperparameter search over one or more estimators with k-fold CV; best (estimator, param map) refitted on the full data.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{evaluationMetric}: Metric name (default 'accuracy')
#'   \item \code{labelCol}: The name of the label column (default 'label')
#'   \item \code{models}: Estimators to sweep
#'   \item \code{numFolds}: CV folds (default 3)
#'   \item \code{numRuns}: Sampled param maps per estimator (default 10)
#'   \item \code{parallelism}: Concurrent candidate fits (default 1)
#'   \item \code{paramSpace}: Per-estimator dict of param Dists, or one shared dict (default None)
#'   \item \code{seed}: RNG seed (sampling + fold split) (default 0)
#'   \item \code{sweepMode}: Candidate execution plane: 'auto' routes batchable candidates through the many-models sweep (shape-bucketed vmapped fits, mmlspark_tpu.sweep) and falls back to the thread pool on any error; 'batched' requires the sweep plane; 'threadpool' forces the sequential candidate-at-a-time baseline (default 'auto')
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_tune_hyperparameters <- function(...) {
  mt_stage("mmlspark_tpu.automl.tune", "TuneHyperparameters", ...)
}

#' TuneHyperparametersModel (Model)
#'
#' A fitted Transformer produced by an Estimator.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{allMetrics}: CV metric per candidate (default None)
#'   \item \code{bestMetric}: Winning CV metric (default nan)
#'   \item \code{bestModel}: Winning fitted model (default None)
#'   \item \code{bestParams}: Winning param map (default None)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_tune_hyperparameters_model <- function(...) {
  mt_stage("mmlspark_tpu.automl.tune", "TuneHyperparametersModel", ...)
}

#' CognitiveServicesBase (Transformer)
#'
#' Base REST transformer. Subclasses define ``urlPath``, declare ServiceParams, and implement ``prepare_entity`` (row dict -> JSON body) — the ``CognitiveServicesBase.prepareEntity`` hook.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{concurrency}: Max in-flight requests (default 4)
#'   \item \code{errorCol}: Error column (default None)
#'   \item \code{maxPollingRetries}: Async poll attempts (default 40)
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{pollingDeadlineMs}: Overall wall-clock budget for one async operation's poll loop; the retry count alone let Retry-After hints stretch the wait unboundedly (default 60000)
#'   \item \code{pollingIntervalMs}: Async poll interval (default 50)
#'   \item \code{subscriptionKey}: API key (value or column) (default None)
#'   \item \code{typed}: Parse responses into the typed schema (default False)
#'   \item \code{url}: Service base URL (default None)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_cognitive_services_base <- function(...) {
  mt_stage("mmlspark_tpu.cognitive.base", "CognitiveServicesBase", ...)
}

#' AddDocuments (Transformer)
#'
#' Push table rows into a search index in batches (``AzureSearch.scala:84-136``). Each batch is one POST of ``{"value": [{"@search.action": ..., <row fields>}, ...]}``.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{actionCol}: Column holding the per-row index action (default None)
#'   \item \code{batchSize}: Documents per request (default 100)
#'   \item \code{concurrency}: Max in-flight requests (default 4)
#'   \item \code{errorCol}: Error column (default None)
#'   \item \code{maxPollingRetries}: Async poll attempts (default 40)
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{pollingDeadlineMs}: Overall wall-clock budget for one async operation's poll loop; the retry count alone let Retry-After hints stretch the wait unboundedly (default 60000)
#'   \item \code{pollingIntervalMs}: Async poll interval (default 50)
#'   \item \code{subscriptionKey}: API key (value or column) (default None)
#'   \item \code{typed}: Parse responses into the typed schema (default False)
#'   \item \code{url}: Service base URL (default None)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_add_documents <- function(...) {
  mt_stage("mmlspark_tpu.cognitive.search", "AddDocuments", ...)
}

#' AnalyzeImage (Transformer)
#'
#' ``cognitive/ComputerVision.scala`` AnalyzeImage.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{concurrency}: Max in-flight requests (default 4)
#'   \item \code{errorCol}: Error column (default None)
#'   \item \code{imageUrlCol}: Column of image URLs (default 'url')
#'   \item \code{maxPollingRetries}: Async poll attempts (default 40)
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{pollingDeadlineMs}: Overall wall-clock budget for one async operation's poll loop; the retry count alone let Retry-After hints stretch the wait unboundedly (default 60000)
#'   \item \code{pollingIntervalMs}: Async poll interval (default 50)
#'   \item \code{subscriptionKey}: API key (value or column) (default None)
#'   \item \code{typed}: Parse responses into the typed schema (default False)
#'   \item \code{url}: Service base URL (default None)
#'   \item \code{visualFeatures}: Comma-joined feature list (default None)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_analyze_image <- function(...) {
  mt_stage("mmlspark_tpu.cognitive.services", "AnalyzeImage", ...)
}

#' BingImageSearch (Transformer)
#'
#' ``cognitive/BingImageSearch.scala:27-66``: GET with query url param.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{concurrency}: Max in-flight requests (default 4)
#'   \item \code{count}: Result count (default None)
#'   \item \code{errorCol}: Error column (default None)
#'   \item \code{maxPollingRetries}: Async poll attempts (default 40)
#'   \item \code{offset}: Result offset (default None)
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{pollingDeadlineMs}: Overall wall-clock budget for one async operation's poll loop; the retry count alone let Retry-After hints stretch the wait unboundedly (default 60000)
#'   \item \code{pollingIntervalMs}: Async poll interval (default 50)
#'   \item \code{queryCol}: Column of search queries (default 'q')
#'   \item \code{subscriptionKey}: API key (value or column) (default None)
#'   \item \code{typed}: Parse responses into the typed schema (default False)
#'   \item \code{url}: Service base URL (default None)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_bing_image_search <- function(...) {
  mt_stage("mmlspark_tpu.cognitive.services", "BingImageSearch", ...)
}

#' DescribeImage (Transformer)
#'
#' ``cognitive/ComputerVision.scala`` DescribeImage.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{concurrency}: Max in-flight requests (default 4)
#'   \item \code{errorCol}: Error column (default None)
#'   \item \code{imageUrlCol}: Column of image URLs (default 'url')
#'   \item \code{maxCandidates}: Caption candidates (default None)
#'   \item \code{maxPollingRetries}: Async poll attempts (default 40)
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{pollingDeadlineMs}: Overall wall-clock budget for one async operation's poll loop; the retry count alone let Retry-After hints stretch the wait unboundedly (default 60000)
#'   \item \code{pollingIntervalMs}: Async poll interval (default 50)
#'   \item \code{subscriptionKey}: API key (value or column) (default None)
#'   \item \code{typed}: Parse responses into the typed schema (default False)
#'   \item \code{url}: Service base URL (default None)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_describe_image <- function(...) {
  mt_stage("mmlspark_tpu.cognitive.services", "DescribeImage", ...)
}

#' DetectAnomalies (Transformer)
#'
#' ``cognitive/AnamolyDetection.scala:23-160`` DetectAnomalies: series of (timestamp, value) points + granularity.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{concurrency}: Max in-flight requests (default 4)
#'   \item \code{errorCol}: Error column (default None)
#'   \item \code{granularity}: Series granularity (default ('value', 'daily'))
#'   \item \code{maxPollingRetries}: Async poll attempts (default 40)
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{pollingDeadlineMs}: Overall wall-clock budget for one async operation's poll loop; the retry count alone let Retry-After hints stretch the wait unboundedly (default 60000)
#'   \item \code{pollingIntervalMs}: Async poll interval (default 50)
#'   \item \code{seriesCol}: Column of point-dict lists (default 'series')
#'   \item \code{subscriptionKey}: API key (value or column) (default None)
#'   \item \code{typed}: Parse responses into the typed schema (default False)
#'   \item \code{url}: Service base URL (default None)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_detect_anomalies <- function(...) {
  mt_stage("mmlspark_tpu.cognitive.services", "DetectAnomalies", ...)
}

#' DetectFace (Transformer)
#'
#' ``cognitive/Face.scala`` DetectFace.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{concurrency}: Max in-flight requests (default 4)
#'   \item \code{errorCol}: Error column (default None)
#'   \item \code{imageUrlCol}: Column of image URLs (default 'url')
#'   \item \code{maxPollingRetries}: Async poll attempts (default 40)
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{pollingDeadlineMs}: Overall wall-clock budget for one async operation's poll loop; the retry count alone let Retry-After hints stretch the wait unboundedly (default 60000)
#'   \item \code{pollingIntervalMs}: Async poll interval (default 50)
#'   \item \code{returnFaceAttributes}: Attribute list (default None)
#'   \item \code{returnFaceLandmarks}: Landmarks flag (default None)
#'   \item \code{subscriptionKey}: API key (value or column) (default None)
#'   \item \code{typed}: Parse responses into the typed schema (default False)
#'   \item \code{url}: Service base URL (default None)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_detect_face <- function(...) {
  mt_stage("mmlspark_tpu.cognitive.services", "DetectFace", ...)
}

#' EntityDetector (Transformer)
#'
#' ``cognitive/TextAnalytics.scala`` EntityDetector.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{concurrency}: Max in-flight requests (default 4)
#'   \item \code{errorCol}: Error column (default None)
#'   \item \code{language}: Language hint (default ('value', 'en'))
#'   \item \code{maxPollingRetries}: Async poll attempts (default 40)
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{pollingDeadlineMs}: Overall wall-clock budget for one async operation's poll loop; the retry count alone let Retry-After hints stretch the wait unboundedly (default 60000)
#'   \item \code{pollingIntervalMs}: Async poll interval (default 50)
#'   \item \code{subscriptionKey}: API key (value or column) (default None)
#'   \item \code{textCol}: Column of input text (default 'text')
#'   \item \code{typed}: Parse responses into the typed schema (default False)
#'   \item \code{url}: Service base URL (default None)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_entity_detector <- function(...) {
  mt_stage("mmlspark_tpu.cognitive.services", "EntityDetector", ...)
}

#' FindSimilarFace (Transformer)
#'
#' ``cognitive/Face.scala`` FindSimilarFace.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{concurrency}: Max in-flight requests (default 4)
#'   \item \code{errorCol}: Error column (default None)
#'   \item \code{faceIdCol}: Column of face ids (default 'faceId')
#'   \item \code{faceIds}: Candidate face id list (default None)
#'   \item \code{maxPollingRetries}: Async poll attempts (default 40)
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{pollingDeadlineMs}: Overall wall-clock budget for one async operation's poll loop; the retry count alone let Retry-After hints stretch the wait unboundedly (default 60000)
#'   \item \code{pollingIntervalMs}: Async poll interval (default 50)
#'   \item \code{subscriptionKey}: API key (value or column) (default None)
#'   \item \code{typed}: Parse responses into the typed schema (default False)
#'   \item \code{url}: Service base URL (default None)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_find_similar_face <- function(...) {
  mt_stage("mmlspark_tpu.cognitive.services", "FindSimilarFace", ...)
}

#' GenerateThumbnails (Transformer)
#'
#' ``cognitive/ComputerVision.scala`` GenerateThumbnails.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{concurrency}: Max in-flight requests (default 4)
#'   \item \code{errorCol}: Error column (default None)
#'   \item \code{height}: Thumb height (default None)
#'   \item \code{imageUrlCol}: Column of image URLs (default 'url')
#'   \item \code{maxPollingRetries}: Async poll attempts (default 40)
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{pollingDeadlineMs}: Overall wall-clock budget for one async operation's poll loop; the retry count alone let Retry-After hints stretch the wait unboundedly (default 60000)
#'   \item \code{pollingIntervalMs}: Async poll interval (default 50)
#'   \item \code{smartCropping}: Smart crop (default None)
#'   \item \code{subscriptionKey}: API key (value or column) (default None)
#'   \item \code{typed}: Parse responses into the typed schema (default False)
#'   \item \code{url}: Service base URL (default None)
#'   \item \code{width}: Thumb width (default None)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_generate_thumbnails <- function(...) {
  mt_stage("mmlspark_tpu.cognitive.services", "GenerateThumbnails", ...)
}

#' GroupFaces (Transformer)
#'
#' ``cognitive/Face.scala`` GroupFaces: cluster face ids by similarity.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{concurrency}: Max in-flight requests (default 4)
#'   \item \code{errorCol}: Error column (default None)
#'   \item \code{faceIdsCol}: Column of face-id lists (default 'faceIds')
#'   \item \code{maxPollingRetries}: Async poll attempts (default 40)
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{pollingDeadlineMs}: Overall wall-clock budget for one async operation's poll loop; the retry count alone let Retry-After hints stretch the wait unboundedly (default 60000)
#'   \item \code{pollingIntervalMs}: Async poll interval (default 50)
#'   \item \code{subscriptionKey}: API key (value or column) (default None)
#'   \item \code{typed}: Parse responses into the typed schema (default False)
#'   \item \code{url}: Service base URL (default None)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_group_faces <- function(...) {
  mt_stage("mmlspark_tpu.cognitive.services", "GroupFaces", ...)
}

#' IdentifyFaces (Transformer)
#'
#' ``cognitive/Face.scala`` IdentifyFaces: match detected faces against a person group.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{concurrency}: Max in-flight requests (default 4)
#'   \item \code{confidenceThreshold}: Match confidence threshold (default None)
#'   \item \code{errorCol}: Error column (default None)
#'   \item \code{faceIdsCol}: Column of face-id lists (default 'faceIds')
#'   \item \code{maxNumOfCandidatesReturned}: Candidate cap (default None)
#'   \item \code{maxPollingRetries}: Async poll attempts (default 40)
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{personGroupId}: Person group to search (default None)
#'   \item \code{pollingDeadlineMs}: Overall wall-clock budget for one async operation's poll loop; the retry count alone let Retry-After hints stretch the wait unboundedly (default 60000)
#'   \item \code{pollingIntervalMs}: Async poll interval (default 50)
#'   \item \code{subscriptionKey}: API key (value or column) (default None)
#'   \item \code{typed}: Parse responses into the typed schema (default False)
#'   \item \code{url}: Service base URL (default None)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_identify_faces <- function(...) {
  mt_stage("mmlspark_tpu.cognitive.services", "IdentifyFaces", ...)
}

#' KeyPhraseExtractor (Transformer)
#'
#' ``cognitive/TextAnalytics.scala`` KeyPhraseExtractor.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{concurrency}: Max in-flight requests (default 4)
#'   \item \code{errorCol}: Error column (default None)
#'   \item \code{language}: Language hint (default ('value', 'en'))
#'   \item \code{maxPollingRetries}: Async poll attempts (default 40)
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{pollingDeadlineMs}: Overall wall-clock budget for one async operation's poll loop; the retry count alone let Retry-After hints stretch the wait unboundedly (default 60000)
#'   \item \code{pollingIntervalMs}: Async poll interval (default 50)
#'   \item \code{subscriptionKey}: API key (value or column) (default None)
#'   \item \code{textCol}: Column of input text (default 'text')
#'   \item \code{typed}: Parse responses into the typed schema (default False)
#'   \item \code{url}: Service base URL (default None)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_key_phrase_extractor <- function(...) {
  mt_stage("mmlspark_tpu.cognitive.services", "KeyPhraseExtractor", ...)
}

#' LanguageDetector (Transformer)
#'
#' ``cognitive/TextAnalytics.scala`` LanguageDetector.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{concurrency}: Max in-flight requests (default 4)
#'   \item \code{errorCol}: Error column (default None)
#'   \item \code{language}: Language hint (default ('value', 'en'))
#'   \item \code{maxPollingRetries}: Async poll attempts (default 40)
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{pollingDeadlineMs}: Overall wall-clock budget for one async operation's poll loop; the retry count alone let Retry-After hints stretch the wait unboundedly (default 60000)
#'   \item \code{pollingIntervalMs}: Async poll interval (default 50)
#'   \item \code{subscriptionKey}: API key (value or column) (default None)
#'   \item \code{textCol}: Column of input text (default 'text')
#'   \item \code{typed}: Parse responses into the typed schema (default False)
#'   \item \code{url}: Service base URL (default None)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_language_detector <- function(...) {
  mt_stage("mmlspark_tpu.cognitive.services", "LanguageDetector", ...)
}

#' NER (Transformer)
#'
#' ``cognitive/TextAnalytics.scala`` NER.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{concurrency}: Max in-flight requests (default 4)
#'   \item \code{errorCol}: Error column (default None)
#'   \item \code{language}: Language hint (default ('value', 'en'))
#'   \item \code{maxPollingRetries}: Async poll attempts (default 40)
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{pollingDeadlineMs}: Overall wall-clock budget for one async operation's poll loop; the retry count alone let Retry-After hints stretch the wait unboundedly (default 60000)
#'   \item \code{pollingIntervalMs}: Async poll interval (default 50)
#'   \item \code{subscriptionKey}: API key (value or column) (default None)
#'   \item \code{textCol}: Column of input text (default 'text')
#'   \item \code{typed}: Parse responses into the typed schema (default False)
#'   \item \code{url}: Service base URL (default None)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_ner <- function(...) {
  mt_stage("mmlspark_tpu.cognitive.services", "NER", ...)
}

#' OCR (Transformer)
#'
#' ``cognitive/ComputerVision.scala`` OCR.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{concurrency}: Max in-flight requests (default 4)
#'   \item \code{detectOrientation}: Detect orientation (default None)
#'   \item \code{errorCol}: Error column (default None)
#'   \item \code{imageUrlCol}: Column of image URLs (default 'url')
#'   \item \code{maxPollingRetries}: Async poll attempts (default 40)
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{pollingDeadlineMs}: Overall wall-clock budget for one async operation's poll loop; the retry count alone let Retry-After hints stretch the wait unboundedly (default 60000)
#'   \item \code{pollingIntervalMs}: Async poll interval (default 50)
#'   \item \code{subscriptionKey}: API key (value or column) (default None)
#'   \item \code{typed}: Parse responses into the typed schema (default False)
#'   \item \code{url}: Service base URL (default None)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_ocr <- function(...) {
  mt_stage("mmlspark_tpu.cognitive.services", "OCR", ...)
}

#' RecognizeText (Transformer)
#'
#' ``cognitive/ComputerVision.scala`` RecognizeText: the REAL async flow — the service answers 202 with an Operation-Location header and the result arrives by polling that URL until a terminal status.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{concurrency}: Max in-flight requests (default 4)
#'   \item \code{errorCol}: Error column (default None)
#'   \item \code{imageUrlCol}: Column of image URLs (default 'url')
#'   \item \code{maxPollingRetries}: Async poll attempts (default 40)
#'   \item \code{mode}: Printed|Handwritten (default None)
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{pollingDeadlineMs}: Overall wall-clock budget for one async operation's poll loop; the retry count alone let Retry-After hints stretch the wait unboundedly (default 60000)
#'   \item \code{pollingIntervalMs}: Async poll interval (default 50)
#'   \item \code{subscriptionKey}: API key (value or column) (default None)
#'   \item \code{typed}: Parse responses into the typed schema (default False)
#'   \item \code{url}: Service base URL (default None)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_recognize_text <- function(...) {
  mt_stage("mmlspark_tpu.cognitive.services", "RecognizeText", ...)
}

#' SpeechToText (Transformer)
#'
#' ``cognitive/SpeechToText.scala`` REST speech recognition: binary audio body in ONE request. For the streaming variant (pull-stream frames over chunked transfer, the Speech SDK transport shape) see :class:`mmlspark_tpu.cognitive.SpeechToTextSDK`.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{audioDataCol}: Column of audio bytes (default 'audio')
#'   \item \code{concurrency}: Max in-flight requests (default 4)
#'   \item \code{errorCol}: Error column (default None)
#'   \item \code{format}: simple|detailed (default None)
#'   \item \code{language}: Recognition language (default ('value', 'en-US'))
#'   \item \code{maxPollingRetries}: Async poll attempts (default 40)
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{pollingDeadlineMs}: Overall wall-clock budget for one async operation's poll loop; the retry count alone let Retry-After hints stretch the wait unboundedly (default 60000)
#'   \item \code{pollingIntervalMs}: Async poll interval (default 50)
#'   \item \code{subscriptionKey}: API key (value or column) (default None)
#'   \item \code{typed}: Parse responses into the typed schema (default False)
#'   \item \code{url}: Service base URL (default None)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_speech_to_text <- function(...) {
  mt_stage("mmlspark_tpu.cognitive.services", "SpeechToText", ...)
}

#' TagImage (Transformer)
#'
#' ``cognitive/ComputerVision.scala`` TagImage.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{concurrency}: Max in-flight requests (default 4)
#'   \item \code{errorCol}: Error column (default None)
#'   \item \code{imageUrlCol}: Column of image URLs (default 'url')
#'   \item \code{maxPollingRetries}: Async poll attempts (default 40)
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{pollingDeadlineMs}: Overall wall-clock budget for one async operation's poll loop; the retry count alone let Retry-After hints stretch the wait unboundedly (default 60000)
#'   \item \code{pollingIntervalMs}: Async poll interval (default 50)
#'   \item \code{subscriptionKey}: API key (value or column) (default None)
#'   \item \code{typed}: Parse responses into the typed schema (default False)
#'   \item \code{url}: Service base URL (default None)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_tag_image <- function(...) {
  mt_stage("mmlspark_tpu.cognitive.services", "TagImage", ...)
}

#' TextSentiment (Transformer)
#'
#' ``cognitive/TextAnalytics.scala`` TextSentiment.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{concurrency}: Max in-flight requests (default 4)
#'   \item \code{errorCol}: Error column (default None)
#'   \item \code{language}: Language hint (default ('value', 'en'))
#'   \item \code{maxPollingRetries}: Async poll attempts (default 40)
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{pollingDeadlineMs}: Overall wall-clock budget for one async operation's poll loop; the retry count alone let Retry-After hints stretch the wait unboundedly (default 60000)
#'   \item \code{pollingIntervalMs}: Async poll interval (default 50)
#'   \item \code{subscriptionKey}: API key (value or column) (default None)
#'   \item \code{textCol}: Column of input text (default 'text')
#'   \item \code{typed}: Parse responses into the typed schema (default False)
#'   \item \code{url}: Service base URL (default None)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_text_sentiment <- function(...) {
  mt_stage("mmlspark_tpu.cognitive.services", "TextSentiment", ...)
}

#' VerifyFaces (Transformer)
#'
#' ``cognitive/Face.scala`` VerifyFaces: same-person check for a pair of face ids (or face id vs person).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{concurrency}: Max in-flight requests (default 4)
#'   \item \code{errorCol}: Error column (default None)
#'   \item \code{faceId1Col}: Column of first face ids (default 'faceId1')
#'   \item \code{faceId2Col}: Column of second face ids (default 'faceId2')
#'   \item \code{maxPollingRetries}: Async poll attempts (default 40)
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{pollingDeadlineMs}: Overall wall-clock budget for one async operation's poll loop; the retry count alone let Retry-After hints stretch the wait unboundedly (default 60000)
#'   \item \code{pollingIntervalMs}: Async poll interval (default 50)
#'   \item \code{subscriptionKey}: API key (value or column) (default None)
#'   \item \code{typed}: Parse responses into the typed schema (default False)
#'   \item \code{url}: Service base URL (default None)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_verify_faces <- function(...) {
  mt_stage("mmlspark_tpu.cognitive.services", "VerifyFaces", ...)
}

#' SpeechToTextSDK (Transformer)
#'
#' Streams audio columns to a speech endpoint in pull-stream frames.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{audioDataCol}: Column of audio bytes (default 'audio')
#'   \item \code{chunkSize}: Streaming frame size in bytes (default 3200 = 100ms of 16kHz PCM) (default 3200)
#'   \item \code{endpointId}: Custom speech model endpoint id (default None)
#'   \item \code{errorCol}: Error column (default None)
#'   \item \code{fileType}: wav|mp3|ogg (default ('value', 'wav'))
#'   \item \code{format}: simple|detailed (default None)
#'   \item \code{language}: Recognition language (default ('value', 'en-US'))
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{profanity}: masked|raw|removed (default None)
#'   \item \code{streamIntermediateResults}: Keep intermediate 'recognizing' events in the output (final 'recognized' events only when False) (default True)
#'   \item \code{subscriptionKey}: API key (value or column) (default None)
#'   \item \code{url}: Service endpoint URL (default None)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_speech_to_text_sdk <- function(...) {
  mt_stage("mmlspark_tpu.cognitive.speech_sdk", "SpeechToTextSDK", ...)
}

#' Estimator (Estimator)
#'
#' Base of all stages. Adds persistence (save/load).
#' @param ... named params forwarded to the Python constructor
#' @export
mt_estimator <- function(...) {
  mt_stage("mmlspark_tpu.core.pipeline", "Estimator", ...)
}

#' Model (Model)
#'
#' A fitted Transformer produced by an Estimator.
#' @param ... named params forwarded to the Python constructor
#' @export
mt_model <- function(...) {
  mt_stage("mmlspark_tpu.core.pipeline", "Model", ...)
}

#' Pipeline (Estimator)
#'
#' Chain of stages; Estimators are fitted in sequence, Transformers pass through — identical semantics to SparkML ``Pipeline.fit``, including the up-front ``transformSchema`` pass: :meth:`validate` threads the column schema through every stage before anything executes, so a mis-wired graph fails in milliseconds instead of after the first TPU compile.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{invalidDataPolicy}: NaN/Inf/label-domain handling at fit: '' (no scan), 'fail', 'drop', or 'impute' (default '')
#'   \item \code{stages}: The chain of pipeline stages (default [])
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_pipeline <- function(...) {
  mt_stage("mmlspark_tpu.core.pipeline", "Pipeline", ...)
}

#' PipelineModel (Model)
#'
#' A fitted Transformer produced by an Estimator.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{stages}: The fitted pipeline stages (default [])
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_pipeline_model <- function(...) {
  mt_stage("mmlspark_tpu.core.pipeline", "PipelineModel", ...)
}

#' PipelineStage (PipelineStage)
#'
#' Base of all stages. Adds persistence (save/load).
#' @param ... named params forwarded to the Python constructor
#' @export
mt_pipeline_stage <- function(...) {
  mt_stage("mmlspark_tpu.core.pipeline", "PipelineStage", ...)
}

#' Transformer (Transformer)
#'
#' Base of all stages. Adds persistence (save/load).
#' @param ... named params forwarded to the Python constructor
#' @export
mt_transformer <- function(...) {
  mt_stage("mmlspark_tpu.core.pipeline", "Transformer", ...)
}

#' DNNModel (Model)
#'
#' Applies a jittable network to feature columns in device batches.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{applyFn}: Jittable (params, {name: array}) -> {name: array} | array (default None)
#'   \item \code{batchSize}: Rows per device batch (static shape; last batch padded) (default 64)
#'   \item \code{feedDict}: model input name -> feature column name (default {})
#'   \item \code{fetchDict}: output column name -> model output name (default {})
#'   \item \code{inputDtype}: Cast inputs to this dtype (default 'float32')
#'   \item \code{meshConfig}: MeshConfig for shardOverMesh (None = all devices on the data axis) (default None)
#'   \item \code{miniBatcher}: Batch rows before eval (CNTKModel batches by default) (default True)
#'   \item \code{modelParams}: Model parameter pytree (default None)
#'   \item \code{paramShardings}: Tensor-parallel map: param key -> axis index sharded over the mesh 'model' axis (None = fully replicated params) (default None)
#'   \item \code{shardOverMesh}: Shard each batch over the mesh 'data' axis (default False)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_dnn_model <- function(...) {
  mt_stage("mmlspark_tpu.dnn.model", "DNNModel", ...)
}

#' CleanMissingData (Estimator)
#'
#' Replace NaN/None with mean, median, or a custom value per column.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{cleaningMode}: Mean, Median, or Custom (default 'Mean')
#'   \item \code{customValue}: Replacement when cleaningMode=Custom (default None)
#'   \item \code{inputCols}: The names of the input columns
#'   \item \code{outputCols}: The names of the output columns
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_clean_missing_data <- function(...) {
  mt_stage("mmlspark_tpu.featurize.clean", "CleanMissingData", ...)
}

#' CleanMissingDataModel (Model)
#'
#' A fitted Transformer produced by an Estimator.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{fillValues}: column -> replacement value (default {})
#'   \item \code{inputCols}: The names of the input columns
#'   \item \code{outputCols}: The names of the output columns
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_clean_missing_data_model <- function(...) {
  mt_stage("mmlspark_tpu.featurize.clean", "CleanMissingDataModel", ...)
}

#' DataConversion (Transformer)
#'
#' Cast the listed columns to ``convertTo``; ``toCategorical`` indexes a column in place (ValueIndexer), ``clearCategorical`` decodes it back.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{convertTo}: Target type (default 'double')
#'   \item \code{dateTimeFormat}: strptime format for string->date (default None)
#'   \item \code{inputCols}: The names of the input columns
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_data_conversion <- function(...) {
  mt_stage("mmlspark_tpu.featurize.conversion", "DataConversion", ...)
}

#' AssembleFeatures (Transformer)
#'
#' Concatenate numeric/vector columns into one 2-D features column (``FastVectorAssembler`` role; categorical metadata is honored by Featurize before assembly).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{inputCols}: The names of the input columns
#'   \item \code{outputCol}: Assembled features column (default 'features')
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_assemble_features <- function(...) {
  mt_stage("mmlspark_tpu.featurize.featurize", "AssembleFeatures", ...)
}

#' Featurize (Estimator)
#'
#' Auto-featurizer: imputes numerics, one-hot (or index) encodes low- cardinality strings, hashes free text, passes vectors through, and assembles everything into ``outputCol``.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{allowImages}: Kept for parity (default False)
#'   \item \code{inputCols}: The names of the input columns
#'   \item \code{numberOfFeatures}: Hash dimensions for text columns (power of two) (default 256)
#'   \item \code{oneHotEncodeCategoricals}: One-hot (true) vs single index column (false) (default True)
#'   \item \code{outputCol}: Features column (default 'features')
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_featurize <- function(...) {
  mt_stage("mmlspark_tpu.featurize.featurize", "Featurize", ...)
}

#' FeaturizeModel (Model)
#'
#' A fitted Transformer produced by an Estimator.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{numberOfFeatures}: Text hash dimensions (default 256)
#'   \item \code{oneHotEncodeCategoricals}: One-hot categoricals (default True)
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{plans}: Per-column featurization plans (default [])
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_featurize_model <- function(...) {
  mt_stage("mmlspark_tpu.featurize.featurize", "FeaturizeModel", ...)
}

#' IndexToValue (Transformer)
#'
#' Inverse of ValueIndexer: index column + categorical metadata -> values (``featurize/IndexToValue.scala:27``).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{inputCol}: The name of the input column
#'   \item \code{outputCol}: The name of the output column
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_index_to_value <- function(...) {
  mt_stage("mmlspark_tpu.featurize.indexers", "IndexToValue", ...)
}

#' ValueIndexer (Estimator)
#'
#' Distinct values -> dense indices [0, n); unseen values map to n (an explicit 'unknown' bucket) at transform time.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{inputCol}: The name of the input column
#'   \item \code{outputCol}: The name of the output column
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_value_indexer <- function(...) {
  mt_stage("mmlspark_tpu.featurize.indexers", "ValueIndexer", ...)
}

#' ValueIndexerModel (Model)
#'
#' A fitted Transformer produced by an Estimator.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{dataType}: Original value dtype (default 'string')
#'   \item \code{inputCol}: The name of the input column
#'   \item \code{levels}: Ordered distinct values (default [])
#'   \item \code{outputCol}: The name of the output column
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_value_indexer_model <- function(...) {
  mt_stage("mmlspark_tpu.featurize.indexers", "ValueIndexerModel", ...)
}

#' LMFeaturizer (Model)
#'
#' Apply a decoder to a column of int32 token rows of one length.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{batchSize}: Rows per device batch (default 4)
#'   \item \code{inputCol}: Column of token-id rows, all of one length (default 'tokens')
#'   \item \code{modelConfig}: Decoder configuration: the family's published config.json keys and 'layers'; 'model_type' names the family ('afmoe' where absent; 'afmoe': mmlspark_tpu.models.afmoe, 'joyai_llm_flash': mmlspark_tpu.models.mla_moe, 'nemotron_h': mmlspark_tpu.models.nemotron_h, 'lfm2_moe': mmlspark_tpu.models.lfm2_moe, 'falcon_h1': mmlspark_tpu.models.falcon_h1, 'KeyeVL2': mmlspark_tpu.models.keye_vl2) (default None)
#'   \item \code{modelParams}: Decoder parameter pytree (the family's init_* format: mmlspark_tpu.models.afmoe.init_afmoe, mmlspark_tpu.models.mla_moe.init_mla_moe, mmlspark_tpu.models.nemotron_h.init_nemotron_h, mmlspark_tpu.models.lfm2_moe.init_lfm2_moe, mmlspark_tpu.models.falcon_h1.init_falcon_h1, mmlspark_tpu.models.keye_vl2.init_keye_vl2) (default None)
#'   \item \code{outputCols}: model output ('hidden': last position after the final norm, 'logits': last-position logits, 'expert_load': tokens of the row each expert received, per expert layer, for a family with experts; 'key_selection': keys kept over the row's queries and the last position's kept keys, per layer, for a family with sparse attention) -> output column (default {'hidden': 'features'})
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_lm_featurizer <- function(...) {
  mt_stage("mmlspark_tpu.featurize.lm", "LMFeaturizer", ...)
}

#' MultiNGram (Transformer)
#'
#' All n-grams for several lengths at once (``featurize/text/MultiNGram.scala:24``). Input: token-list column.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{inputCol}: The name of the input column
#'   \item \code{lengths}: N-gram lengths (default [1, 2, 3])
#'   \item \code{outputCol}: The name of the output column
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_multi_n_gram <- function(...) {
  mt_stage("mmlspark_tpu.featurize.text", "MultiNGram", ...)
}

#' PageSplitter (Transformer)
#'
#' Split documents into pages within [minimum, maximum] character budget, preferring boundaries (``featurize/text/PageSplitter.scala:20``). Output is a ragged column of page-string lists.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{boundaryRegex}: Soft boundary (default '\\s')
#'   \item \code{inputCol}: The name of the input column
#'   \item \code{maximumPageLength}: Max characters per page (default 5000)
#'   \item \code{minimumPageLength}: Min characters before a soft boundary split (default 4500)
#'   \item \code{outputCol}: The name of the output column
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_page_splitter <- function(...) {
  mt_stage("mmlspark_tpu.featurize.text", "PageSplitter", ...)
}

#' TextFeaturizer (Estimator)
#'
#' tokenize -> n-grams -> hashingTF -> IDF, one estimator (``featurize/text/TextFeaturizer.scala:181``).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{binary}: Binary term frequencies (default False)
#'   \item \code{inputCol}: The name of the input column
#'   \item \code{minDocFreq}: Min documents for IDF terms (default 0)
#'   \item \code{minTokenLength}: Drop shorter tokens (default 0)
#'   \item \code{nGramLength}: N-gram length (default 2)
#'   \item \code{numFeatures}: Hash space size (power of two). TF blocks are dense 2-D columns, so memory is n_docs x numFeatures x 4 bytes — size accordingly (default 4096)
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{toLowercase}: Lowercase before tokenizing (default True)
#'   \item \code{tokenizerPattern}: Token split regex (default '\\s+')
#'   \item \code{useIDF}: Rescale by inverse document frequency (default True)
#'   \item \code{useNGram}: Add n-grams (default False)
#'   \item \code{useTokenizer}: Tokenize the input (default True)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_text_featurizer <- function(...) {
  mt_stage("mmlspark_tpu.featurize.text", "TextFeaturizer", ...)
}

#' TextFeaturizerModel (Model)
#'
#' A fitted Transformer produced by an Estimator.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{binary}: Binary term frequencies (default False)
#'   \item \code{idfVector}: IDF weights (None = raw TF) (default None)
#'   \item \code{inputCol}: The name of the input column
#'   \item \code{minTokenLength}: Drop shorter tokens (default 0)
#'   \item \code{nGramLength}: N-gram length (default 2)
#'   \item \code{numFeatures}: Hash space size (default 4096)
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{toLowercase}: Lowercase before tokenizing (default True)
#'   \item \code{tokenizerPattern}: Token split regex (default '\\s+')
#'   \item \code{useNGram}: Add n-grams (default False)
#'   \item \code{useTokenizer}: Tokenize the input (default True)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_text_featurizer_model <- function(...) {
  mt_stage("mmlspark_tpu.featurize.text", "TextFeaturizerModel", ...)
}

#' ImageFeaturizer (Model)
#'
#' Featurize an image column with a (cut) deep network.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{applyFn}: Backbone (params, x, cut) -> array; default resnet_apply (default None)
#'   \item \code{autoResize}: Resize images to the model input (ResizeImageTransformer analogue) (default True)
#'   \item \code{batchSize}: Device batch size (default 64)
#'   \item \code{cutOutputLayers}: Layers cut from the top: 0 = logits (headful), 1 = pooled features (reference default), 2 = feature map (default 1)
#'   \item \code{inputCol}: Image column (default 'image')
#'   \item \code{inputHeight}: Model input height (default 32)
#'   \item \code{inputWidth}: Model input width (default 32)
#'   \item \code{modelParams}: Backbone parameter pytree (mmlspark_tpu.models zoo format) (default None)
#'   \item \code{outputCol}: Feature vector column (default 'features')
#'   \item \code{scale}: Pixel scale applied before the backbone (default 0.00392156862745098)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_image_featurizer <- function(...) {
  mt_stage("mmlspark_tpu.image.featurizer", "ImageFeaturizer", ...)
}

#' ImageSetAugmenter (Transformer)
#'
#' Flip-based dataset augmentation (``image/ImageSetAugmenter.scala``): emits the original rows plus a flipped copy per enabled axis.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{flipLeftRight}: Mirror horizontally (default True)
#'   \item \code{flipUpDown}: Mirror vertically (default False)
#'   \item \code{inputCol}: Image column (default 'image')
#'   \item \code{outputCol}: Output image column (default 'image')
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_image_set_augmenter <- function(...) {
  mt_stage("mmlspark_tpu.image.transforms", "ImageSetAugmenter", ...)
}

#' ImageTransformer (Transformer)
#'
#' Applies a list of image stages to an image column.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{inputCol}: Image column (default 'image')
#'   \item \code{outputCol}: Output image column (default 'image_out')
#'   \item \code{stages}: List of {'op': name, ...} stage dicts (default [])
#'   \item \code{toFloat}: Emit float32 images (skip uint8 round-trip) (default False)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_image_transformer <- function(...) {
  mt_stage("mmlspark_tpu.image.transforms", "ImageTransformer", ...)
}

#' UnrollImage (Transformer)
#'
#' Base of all stages. Adds persistence (save/load).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{inputCol}: Image column (default 'image')
#'   \item \code{outputCol}: Unrolled vector column (default 'unrolled')
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_unroll_image <- function(...) {
  mt_stage("mmlspark_tpu.image.unroll", "UnrollImage", ...)
}

#' CustomInputParser (Transformer)
#'
#' UDF row -> request (``Parsers.scala:79-109``).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{inputCol}: The name of the input column
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{udf}: value -> HTTPRequestData function (default None)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_custom_input_parser <- function(...) {
  mt_stage("mmlspark_tpu.io.http.transformers", "CustomInputParser", ...)
}

#' CustomOutputParser (Transformer)
#'
#' UDF response -> value (``Parsers.scala:191-232``).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{inputCol}: The name of the input column
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{udf}: HTTPResponseData -> value function (default None)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_custom_output_parser <- function(...) {
  mt_stage("mmlspark_tpu.io.http.transformers", "CustomOutputParser", ...)
}

#' HTTPTransformer (Transformer)
#'
#' Column of :class:`HTTPRequestData` -> column of :class:`HTTPResponseData`, sent with bounded concurrency.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{concurrency}: Max in-flight requests (default 8)
#'   \item \code{inputCol}: The name of the input column
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{timeout}: Per-request timeout seconds (default 60.0)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_http_transformer <- function(...) {
  mt_stage("mmlspark_tpu.io.http.transformers", "HTTPTransformer", ...)
}

#' JSONInputParser (Transformer)
#'
#' Row value -> JSON POST :class:`HTTPRequestData` (``Parsers.scala:24-77``).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{headers}: Extra headers dict (default None)
#'   \item \code{inputCol}: The name of the input column
#'   \item \code{method}: HTTP method (default 'POST')
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{url}: Target URL
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_json_input_parser <- function(...) {
  mt_stage("mmlspark_tpu.io.http.transformers", "JSONInputParser", ...)
}

#' JSONOutputParser (Transformer)
#'
#' Response -> parsed JSON object column (``Parsers.scala:111-160``).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{inputCol}: The name of the input column
#'   \item \code{outputCol}: The name of the output column
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_json_output_parser <- function(...) {
  mt_stage("mmlspark_tpu.io.http.transformers", "JSONOutputParser", ...)
}

#' PartitionConsolidator (Transformer)
#'
#' Rate-limit-friendly funnel: all rows share one client with a global concurrency cap (``PartitionConsolidator.scala:17-132`` routed many partitions through few executor-shared connections; with columnar Tables the consolidation is the single shared AsyncHTTPClient).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{concurrency}: Global in-flight cap (default 1)
#'   \item \code{inputCol}: The name of the input column
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{timeout}: Per-request timeout seconds (default 60.0)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_partition_consolidator <- function(...) {
  mt_stage("mmlspark_tpu.io.http.transformers", "PartitionConsolidator", ...)
}

#' SimpleHTTPTransformer (Transformer)
#'
#' inputParser -> HTTPTransformer -> outputParser, with failed rows (non-2xx) routed to ``errorCol`` instead of the output (``SimpleHTTPTransformer.scala:64-166``).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{concurrency}: Max in-flight requests (default 8)
#'   \item \code{errorCol}: Error column name (default None)
#'   \item \code{inputCol}: The name of the input column
#'   \item \code{inputParser}: Transformer producing HTTPRequestData (default None)
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{outputParser}: Transformer consuming HTTPResponseData (default None)
#'   \item \code{timeout}: Per-request timeout seconds (default 60.0)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_simple_http_transformer <- function(...) {
  mt_stage("mmlspark_tpu.io.http.transformers", "SimpleHTTPTransformer", ...)
}

#' StringOutputParser (Transformer)
#'
#' Response -> body text column (``Parsers.scala:162-189``).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{inputCol}: The name of the input column
#'   \item \code{outputCol}: The name of the output column
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_string_output_parser <- function(...) {
  mt_stage("mmlspark_tpu.io.http.transformers", "StringOutputParser", ...)
}

#' PowerBIWriter (Transformer)
#'
#' Pipeline-stage wrapper: passes the table through unchanged after pushing it (the streaming-sink usage of ``PowerBIWriter.scala``).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{batchSize}: Rows per POST (default 100)
#'   \item \code{url}: PowerBI push-dataset URL (default None)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_power_bi_writer <- function(...) {
  mt_stage("mmlspark_tpu.io.powerbi", "PowerBIWriter", ...)
}

#' IsolationForest (Estimator)
#'
#' Param surface mirrors LinkedIn's ``IsolationForestParams``.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{bootstrap}: Sample with replacement (default False)
#'   \item \code{contamination}: Expected outlier fraction (0 = use scoreThreshold) (default 0.0)
#'   \item \code{featuresCol}: The name of the features column (default 'features')
#'   \item \code{maxFeatures}: Feature subsample per tree (<=1.0: fraction) (default 1.0)
#'   \item \code{maxSamples}: Subsample size per tree (<=1.0: fraction of rows) (default 256.0)
#'   \item \code{numEstimators}: Number of isolation trees (default 100)
#'   \item \code{predictionCol}: The name of the prediction column (default 'prediction')
#'   \item \code{randomSeed}: RNG seed (default 1)
#'   \item \code{scoreCol}: Output anomaly-score column (default 'outlierScore')
#'   \item \code{scoreThreshold}: Outlier score cut when contamination=0 (default 0.5)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_isolation_forest <- function(...) {
  mt_stage("mmlspark_tpu.isolationforest.forest", "IsolationForest", ...)
}

#' IsolationForestModel (Model)
#'
#' A fitted Transformer produced by an Estimator.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{featuresCol}: The name of the features column (default 'features')
#'   \item \code{maxDepth}: Tree height cap (default 8)
#'   \item \code{numSamples}: Per-tree subsample size (default 256)
#'   \item \code{outlierScoreThreshold}: Score cut for predictedLabel (default 0.5)
#'   \item \code{predictionCol}: The name of the prediction column (default 'prediction')
#'   \item \code{scoreCol}: Output anomaly-score column (default 'outlierScore')
#'   \item \code{trees}: Packed tree arrays (default None)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_isolation_forest_model <- function(...) {
  mt_stage("mmlspark_tpu.isolationforest.forest", "IsolationForestModel", ...)
}

#' LightGBMBase (Estimator)
#'
#' Shared fit flow (LightGBMBase.scala:26-213).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{baggingFraction}: Row subsample fraction (default 1.0)
#'   \item \code{baggingFreq}: Resample bagging mask every k iterations (0=off) (default 0)
#'   \item \code{baggingSeed}: Bagging seed (default 3)
#'   \item \code{binSampleCount}: Rows sampled when computing histogram bin edges (bin_construct_sample_cnt) (default 200000)
#'   \item \code{boostFromAverage}: Start boosting from the label average init score (false = from 0) (default True)
#'   \item \code{boostingType}: gbdt, rf, dart, or goss (default 'gbdt')
#'   \item \code{catL2}: Extra L2 applied to categorical split gains (default 10.0)
#'   \item \code{catSmooth}: Smoothing for the categorical g/h bin ordering (default 10.0)
#'   \item \code{categoricalSlotIndexes}: Feature indexes treated as categorical (value-identity bins + LightGBM sorted-set split search) (default [])
#'   \item \code{categoricalSlotNames}: Feature names treated as categorical (resolved against the assembled feature names, e.g. 'f3') (default [])
#'   \item \code{dropRate}: DART: per-tree dropout probability (default 0.1)
#'   \item \code{earlyStoppingRound}: Stop after k rounds without improvement (0=off) (default 0)
#'   \item \code{featureBundling}: Exclusive Feature Bundling (native enable_bundle): greedily pack (near-)mutually-exclusive features into shared bin columns at binning time. Shrinks K = sum_f bins_f — the HBM re-stream that bounds every histogram pass — and the column count, so sparse/one-hot matrices fit the precomputed-U budget at row counts that previously overflowed it. Splits, model text, SHAP, and prediction stay in original feature space (emitted models are indistinguishable from unbundled fits; with zero bundling conflicts the tree structure is identical). Off by default — the native engine defaults on, but bundled histogram g/h for a member's default bin are recovered by subtraction, so float leaf values can differ in the last ulp from an unbundled fit (default False)
#'   \item \code{featureFraction}: Feature subsample fraction per tree (default 1.0)
#'   \item \code{featuresCol}: The name of the features column (default 'features')
#'   \item \code{featuresShapCol}: Output column for SHAP values ('' = off) (default '')
#'   \item \code{growthPolicy}: leafwise (LightGBM best-first, numLeaves-bounded) or depthwise (balanced levels — fewer, larger MXU passes) (default 'leafwise')
#'   \item \code{improvementTolerance}: Minimal delta counted as improvement (default 0.0)
#'   \item \code{initScoreCol}: The name of the initial-score (margin) column for warm start
#'   \item \code{isProvideTrainingMetric}: Record the train-set metric each iteration (evals['training']) (default False)
#'   \item \code{labelCol}: The name of the label column (default 'label')
#'   \item \code{lambdaL1}: L1 regularization (default 0.0)
#'   \item \code{lambdaL2}: L2 regularization (default 0.0)
#'   \item \code{leafBatch}: Frontier leaves split per histogram pass under leafwise growth. NOTE: the default (8) is a batched APPROXIMATION of LightGBM's sequential best-first growth — up to 8 frontier leaves commit together, so default fits are not best-first-exact and differ slightly from the native engine's trees (bench AUC delta ~0.001, docs/perf_histogram.md). Set leafBatch=1 for the exact sequential algorithm (~4x slower), or leafBatchRatio=1.0 to keep batching only for exact gain ties. >1 costs ~one pass via the panel kernel (default 8)
#'   \item \code{leafBatchRatio}: Only batch leaves whose gain >= ratio * pass-best (0 = off; 1.0 reproduces exact best-first; ~0.2 measured to IMPROVE holdout AUC past both exact best-first and the CPU engine at ~20\% extra fit time — docs/perf_histogram.md) (default 0.0)
#'   \item \code{leafPredictionCol}: Output column for leaf indices ('' = off) (default '')
#'   \item \code{learningRate}: Shrinkage rate (default 0.1)
#'   \item \code{maxBin}: Max number of feature bins (default 255)
#'   \item \code{maxBinByFeature}: Per-feature max-bin override (empty = maxBin everywhere) (default [])
#'   \item \code{maxCatThreshold}: Max categories in a categorical split's left set (default 32)
#'   \item \code{maxCatToOnehot}: Categorical features with at most this many seen categories use the one-vs-rest split search instead of the sorted-set algorithm (native LightGBM max_cat_to_onehot) (default 4)
#'   \item \code{maxConflictRate}: EFB conflict budget (native max_conflict_rate): fraction of sampled rows where two bundled features may be simultaneously non-default. 0.0 = only perfectly exclusive features bundle (lossless); small values (e.g. 0.05) bundle harder at a bounded accuracy cost on conflict rows (default 0.0)
#'   \item \code{maxDeltaStep}: Max leaf output magnitude (0=off) (default 0.0)
#'   \item \code{maxDepth}: Max tree depth (-1 = derive from numLeaves) (default -1)
#'   \item \code{metric}: Eval metric name ('' = objective default) (default '')
#'   \item \code{minDataInLeaf}: Minimum rows per leaf (default 20)
#'   \item \code{minDataPerGroup}: Minimal rows a category needs to enter the sorted-set split search (native LightGBM min_data_per_group; the one-vs-rest path is exempt) (default 100)
#'   \item \code{minGainToSplit}: Minimum gain to split (default 0.0)
#'   \item \code{minSumHessianInLeaf}: Minimum hessian sum per leaf (default 0.001)
#'   \item \code{modelString}: Warm-start booster string (default '')
#'   \item \code{negBaggingFraction}: Negative-class bagging fraction (binary; 1.0 = off) (default 1.0)
#'   \item \code{numBatches}: Split training into sequential batches (0=off) (default 0)
#'   \item \code{numExecutors}: Run the histogram-binning prepass as partitioned tasks on this many fault-tolerant executors (mmlspark_tpu.runtime): bounded retries, heartbeat-loss re-dispatch, and lineage recompute apply, and the binned matrix is bit-identical to the inline pass. 0 (default) bins inline; an ambient runtime.policy() also activates the scheduler (default 0)
#'   \item \code{numIterations}: Number of boosting iterations (default 100)
#'   \item \code{numLeaves}: Max leaves per tree (default 31)
#'   \item \code{numProcesses}: Run the fit itself across this many real worker processes under a supervised gang (mmlspark_tpu.runtime.procgroup): each process fits a contiguous row shard, histograms allreduce over sockets, and a process killed mid-fit triggers gang recovery that resumes from the fit journal with zero re-execution of committed iterations. The distributed analog of the reference's per-executor native fit. 0/1 (default) fits in-process. Process mode restricts options (no bagging/GOSS/dart, no validation sets); see lightgbm.procfit.validate_process_options (default 0)
#'   \item \code{numTasks}: Override number of mesh shards (0 = all devices) (default 0)
#'   \item \code{otherRate}: GOSS: sampled fraction of remaining rows (default 0.1)
#'   \item \code{parallelism}: data_parallel, voting_parallel, or serial (default 'data_parallel')
#'   \item \code{posBaggingFraction}: Positive-class bagging fraction (binary; 1.0 = off) (default 1.0)
#'   \item \code{predictionCol}: The name of the prediction column (default 'prediction')
#'   \item \code{seed}: Master seed (default 0)
#'   \item \code{slotNames}: Feature slot names (overrides the generated f0..fN; also the namespace categoricalSlotNames resolves against) (default [])
#'   \item \code{topK}: Top features for voting parallel (default 20)
#'   \item \code{topRate}: GOSS: kept fraction of large-gradient rows (default 0.2)
#'   \item \code{useQuantizedGrad}: LightGBM's gradient-quantization training (use_quantized_grad): stochastically round g/h to an 8-bit per-tree grid so the histogram pass runs on the integer MXU (~15\% faster fits at the bench shape, docs/perf_histogram.md). Per-bin sums stay unbiased and counts exact; off (default) keeps bit-exact bf16 stats. Requires the precomputed-U path (single-device, maxBin <= 255, U within the HBM budget) and < 2^24 rows (f32 count exactness) — otherwise training logs a warning and proceeds with exact stats. Depthwise fits with depth >= 7 exceed the 128-slot U panel budget on deep levels (> 42 frontier nodes) and fall back per-level to exact histograms, logged once per fit (default False)
#'   \item \code{useSingleDatasetMode}: Accepted for API parity (dataset is always host-resident) (default True)
#'   \item \code{validationIndicatorCol}: Boolean column marking rows used for validation / early stopping
#'   \item \code{verbosity}: Verbosity (default -1)
#'   \item \code{weightCol}: The name of the instance-weight column
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_light_gbm_base <- function(...) {
  mt_stage("mmlspark_tpu.lightgbm.base", "LightGBMBase", ...)
}

#' LightGBMModelBase (Model)
#'
#' Shared model surface: booster access, native-model serde, leaf output.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{boosterData}: Fitted booster state
#'   \item \code{featuresCol}: The name of the features column (default 'features')
#'   \item \code{featuresShapCol}: Output column for SHAP values ('' = off) (default '')
#'   \item \code{leafPredictionCol}: Output column for leaf indices ('' = off) (default '')
#'   \item \code{predictionCol}: The name of the prediction column (default 'prediction')
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_light_gbm_model_base <- function(...) {
  mt_stage("mmlspark_tpu.lightgbm.base", "LightGBMModelBase", ...)
}

#' LightGBMClassificationModel (Model)
#'
#' Shared model surface: booster access, native-model serde, leaf output.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{boosterData}: Fitted booster state
#'   \item \code{featuresCol}: The name of the features column (default 'features')
#'   \item \code{featuresShapCol}: Output column for SHAP values ('' = off) (default '')
#'   \item \code{leafPredictionCol}: Output column for leaf indices ('' = off) (default '')
#'   \item \code{numClasses}: Number of classes (default 2)
#'   \item \code{predictionCol}: The name of the prediction column (default 'prediction')
#'   \item \code{probabilityCol}: Probability output column (default 'probability')
#'   \item \code{rawPredictionCol}: Raw margin output column (default 'rawPrediction')
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_light_gbm_classification_model <- function(...) {
  mt_stage("mmlspark_tpu.lightgbm.classifier", "LightGBMClassificationModel", ...)
}

#' LightGBMClassifier (Estimator)
#'
#' Shared fit flow (LightGBMBase.scala:26-213).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{baggingFraction}: Row subsample fraction (default 1.0)
#'   \item \code{baggingFreq}: Resample bagging mask every k iterations (0=off) (default 0)
#'   \item \code{baggingSeed}: Bagging seed (default 3)
#'   \item \code{binSampleCount}: Rows sampled when computing histogram bin edges (bin_construct_sample_cnt) (default 200000)
#'   \item \code{boostFromAverage}: Start boosting from the label average init score (false = from 0) (default True)
#'   \item \code{boostingType}: gbdt, rf, dart, or goss (default 'gbdt')
#'   \item \code{catL2}: Extra L2 applied to categorical split gains (default 10.0)
#'   \item \code{catSmooth}: Smoothing for the categorical g/h bin ordering (default 10.0)
#'   \item \code{categoricalSlotIndexes}: Feature indexes treated as categorical (value-identity bins + LightGBM sorted-set split search) (default [])
#'   \item \code{categoricalSlotNames}: Feature names treated as categorical (resolved against the assembled feature names, e.g. 'f3') (default [])
#'   \item \code{dropRate}: DART: per-tree dropout probability (default 0.1)
#'   \item \code{earlyStoppingRound}: Stop after k rounds without improvement (0=off) (default 0)
#'   \item \code{featureBundling}: Exclusive Feature Bundling (native enable_bundle): greedily pack (near-)mutually-exclusive features into shared bin columns at binning time. Shrinks K = sum_f bins_f — the HBM re-stream that bounds every histogram pass — and the column count, so sparse/one-hot matrices fit the precomputed-U budget at row counts that previously overflowed it. Splits, model text, SHAP, and prediction stay in original feature space (emitted models are indistinguishable from unbundled fits; with zero bundling conflicts the tree structure is identical). Off by default — the native engine defaults on, but bundled histogram g/h for a member's default bin are recovered by subtraction, so float leaf values can differ in the last ulp from an unbundled fit (default False)
#'   \item \code{featureFraction}: Feature subsample fraction per tree (default 1.0)
#'   \item \code{featuresCol}: The name of the features column (default 'features')
#'   \item \code{featuresShapCol}: Output column for SHAP values ('' = off) (default '')
#'   \item \code{growthPolicy}: leafwise (LightGBM best-first, numLeaves-bounded) or depthwise (balanced levels — fewer, larger MXU passes) (default 'leafwise')
#'   \item \code{improvementTolerance}: Minimal delta counted as improvement (default 0.0)
#'   \item \code{initScoreCol}: The name of the initial-score (margin) column for warm start
#'   \item \code{isProvideTrainingMetric}: Record the train-set metric each iteration (evals['training']) (default False)
#'   \item \code{isUnbalance}: Binary class weighting for unbalanced data: positive rows get weight n_neg/n_pos (native is_unbalance, LightGBMClassifier.scala:32) (default False)
#'   \item \code{labelCol}: The name of the label column (default 'label')
#'   \item \code{lambdaL1}: L1 regularization (default 0.0)
#'   \item \code{lambdaL2}: L2 regularization (default 0.0)
#'   \item \code{leafBatch}: Frontier leaves split per histogram pass under leafwise growth. NOTE: the default (8) is a batched APPROXIMATION of LightGBM's sequential best-first growth — up to 8 frontier leaves commit together, so default fits are not best-first-exact and differ slightly from the native engine's trees (bench AUC delta ~0.001, docs/perf_histogram.md). Set leafBatch=1 for the exact sequential algorithm (~4x slower), or leafBatchRatio=1.0 to keep batching only for exact gain ties. >1 costs ~one pass via the panel kernel (default 8)
#'   \item \code{leafBatchRatio}: Only batch leaves whose gain >= ratio * pass-best (0 = off; 1.0 reproduces exact best-first; ~0.2 measured to IMPROVE holdout AUC past both exact best-first and the CPU engine at ~20\% extra fit time — docs/perf_histogram.md) (default 0.0)
#'   \item \code{leafPredictionCol}: Output column for leaf indices ('' = off) (default '')
#'   \item \code{learningRate}: Shrinkage rate (default 0.1)
#'   \item \code{maxBin}: Max number of feature bins (default 255)
#'   \item \code{maxBinByFeature}: Per-feature max-bin override (empty = maxBin everywhere) (default [])
#'   \item \code{maxCatThreshold}: Max categories in a categorical split's left set (default 32)
#'   \item \code{maxCatToOnehot}: Categorical features with at most this many seen categories use the one-vs-rest split search instead of the sorted-set algorithm (native LightGBM max_cat_to_onehot) (default 4)
#'   \item \code{maxConflictRate}: EFB conflict budget (native max_conflict_rate): fraction of sampled rows where two bundled features may be simultaneously non-default. 0.0 = only perfectly exclusive features bundle (lossless); small values (e.g. 0.05) bundle harder at a bounded accuracy cost on conflict rows (default 0.0)
#'   \item \code{maxDeltaStep}: Max leaf output magnitude (0=off) (default 0.0)
#'   \item \code{maxDepth}: Max tree depth (-1 = derive from numLeaves) (default -1)
#'   \item \code{metric}: Eval metric name ('' = objective default) (default '')
#'   \item \code{minDataInLeaf}: Minimum rows per leaf (default 20)
#'   \item \code{minDataPerGroup}: Minimal rows a category needs to enter the sorted-set split search (native LightGBM min_data_per_group; the one-vs-rest path is exempt) (default 100)
#'   \item \code{minGainToSplit}: Minimum gain to split (default 0.0)
#'   \item \code{minSumHessianInLeaf}: Minimum hessian sum per leaf (default 0.001)
#'   \item \code{modelString}: Warm-start booster string (default '')
#'   \item \code{negBaggingFraction}: Negative-class bagging fraction (binary; 1.0 = off) (default 1.0)
#'   \item \code{numBatches}: Split training into sequential batches (0=off) (default 0)
#'   \item \code{numExecutors}: Run the histogram-binning prepass as partitioned tasks on this many fault-tolerant executors (mmlspark_tpu.runtime): bounded retries, heartbeat-loss re-dispatch, and lineage recompute apply, and the binned matrix is bit-identical to the inline pass. 0 (default) bins inline; an ambient runtime.policy() also activates the scheduler (default 0)
#'   \item \code{numIterations}: Number of boosting iterations (default 100)
#'   \item \code{numLeaves}: Max leaves per tree (default 31)
#'   \item \code{numProcesses}: Run the fit itself across this many real worker processes under a supervised gang (mmlspark_tpu.runtime.procgroup): each process fits a contiguous row shard, histograms allreduce over sockets, and a process killed mid-fit triggers gang recovery that resumes from the fit journal with zero re-execution of committed iterations. The distributed analog of the reference's per-executor native fit. 0/1 (default) fits in-process. Process mode restricts options (no bagging/GOSS/dart, no validation sets); see lightgbm.procfit.validate_process_options (default 0)
#'   \item \code{numTasks}: Override number of mesh shards (0 = all devices) (default 0)
#'   \item \code{objective}: binary or multiclass ('' = infer from label arity) (default '')
#'   \item \code{otherRate}: GOSS: sampled fraction of remaining rows (default 0.1)
#'   \item \code{parallelism}: data_parallel, voting_parallel, or serial (default 'data_parallel')
#'   \item \code{posBaggingFraction}: Positive-class bagging fraction (binary; 1.0 = off) (default 1.0)
#'   \item \code{predictionCol}: The name of the prediction column (default 'prediction')
#'   \item \code{probabilityCol}: Probability output column (default 'probability')
#'   \item \code{rawPredictionCol}: Raw margin output column (default 'rawPrediction')
#'   \item \code{seed}: Master seed (default 0)
#'   \item \code{slotNames}: Feature slot names (overrides the generated f0..fN; also the namespace categoricalSlotNames resolves against) (default [])
#'   \item \code{topK}: Top features for voting parallel (default 20)
#'   \item \code{topRate}: GOSS: kept fraction of large-gradient rows (default 0.2)
#'   \item \code{useQuantizedGrad}: LightGBM's gradient-quantization training (use_quantized_grad): stochastically round g/h to an 8-bit per-tree grid so the histogram pass runs on the integer MXU (~15\% faster fits at the bench shape, docs/perf_histogram.md). Per-bin sums stay unbiased and counts exact; off (default) keeps bit-exact bf16 stats. Requires the precomputed-U path (single-device, maxBin <= 255, U within the HBM budget) and < 2^24 rows (f32 count exactness) — otherwise training logs a warning and proceeds with exact stats. Depthwise fits with depth >= 7 exceed the 128-slot U panel budget on deep levels (> 42 frontier nodes) and fall back per-level to exact histograms, logged once per fit (default False)
#'   \item \code{useSingleDatasetMode}: Accepted for API parity (dataset is always host-resident) (default True)
#'   \item \code{validationIndicatorCol}: Boolean column marking rows used for validation / early stopping
#'   \item \code{verbosity}: Verbosity (default -1)
#'   \item \code{weightCol}: The name of the instance-weight column
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_light_gbm_classifier <- function(...) {
  mt_stage("mmlspark_tpu.lightgbm.classifier", "LightGBMClassifier", ...)
}

#' LightGBMRanker (Estimator)
#'
#' Shared fit flow (LightGBMBase.scala:26-213).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{baggingFraction}: Row subsample fraction (default 1.0)
#'   \item \code{baggingFreq}: Resample bagging mask every k iterations (0=off) (default 0)
#'   \item \code{baggingSeed}: Bagging seed (default 3)
#'   \item \code{binSampleCount}: Rows sampled when computing histogram bin edges (bin_construct_sample_cnt) (default 200000)
#'   \item \code{boostFromAverage}: Start boosting from the label average init score (false = from 0) (default True)
#'   \item \code{boostingType}: gbdt, rf, dart, or goss (default 'gbdt')
#'   \item \code{catL2}: Extra L2 applied to categorical split gains (default 10.0)
#'   \item \code{catSmooth}: Smoothing for the categorical g/h bin ordering (default 10.0)
#'   \item \code{categoricalSlotIndexes}: Feature indexes treated as categorical (value-identity bins + LightGBM sorted-set split search) (default [])
#'   \item \code{categoricalSlotNames}: Feature names treated as categorical (resolved against the assembled feature names, e.g. 'f3') (default [])
#'   \item \code{dropRate}: DART: per-tree dropout probability (default 0.1)
#'   \item \code{earlyStoppingRound}: Stop after k rounds without improvement (0=off) (default 0)
#'   \item \code{evalAt}: NDCG truncation for eval (default 5)
#'   \item \code{featureBundling}: Exclusive Feature Bundling (native enable_bundle): greedily pack (near-)mutually-exclusive features into shared bin columns at binning time. Shrinks K = sum_f bins_f — the HBM re-stream that bounds every histogram pass — and the column count, so sparse/one-hot matrices fit the precomputed-U budget at row counts that previously overflowed it. Splits, model text, SHAP, and prediction stay in original feature space (emitted models are indistinguishable from unbundled fits; with zero bundling conflicts the tree structure is identical). Off by default — the native engine defaults on, but bundled histogram g/h for a member's default bin are recovered by subtraction, so float leaf values can differ in the last ulp from an unbundled fit (default False)
#'   \item \code{featureFraction}: Feature subsample fraction per tree (default 1.0)
#'   \item \code{featuresCol}: The name of the features column (default 'features')
#'   \item \code{featuresShapCol}: Output column for SHAP values ('' = off) (default '')
#'   \item \code{groupCol}: The name of the query-group column (ranking)
#'   \item \code{growthPolicy}: leafwise (LightGBM best-first, numLeaves-bounded) or depthwise (balanced levels — fewer, larger MXU passes) (default 'leafwise')
#'   \item \code{improvementTolerance}: Minimal delta counted as improvement (default 0.0)
#'   \item \code{initScoreCol}: The name of the initial-score (margin) column for warm start
#'   \item \code{isProvideTrainingMetric}: Record the train-set metric each iteration (evals['training']) (default False)
#'   \item \code{labelCol}: The name of the label column (default 'label')
#'   \item \code{labelGain}: Relevance->gain table for the lambdarank objective and ndcg eval (empty = LightGBM's default 2^i - 1); indexed by the integer relevance label (default [])
#'   \item \code{lambdaL1}: L1 regularization (default 0.0)
#'   \item \code{lambdaL2}: L2 regularization (default 0.0)
#'   \item \code{leafBatch}: Frontier leaves split per histogram pass under leafwise growth. NOTE: the default (8) is a batched APPROXIMATION of LightGBM's sequential best-first growth — up to 8 frontier leaves commit together, so default fits are not best-first-exact and differ slightly from the native engine's trees (bench AUC delta ~0.001, docs/perf_histogram.md). Set leafBatch=1 for the exact sequential algorithm (~4x slower), or leafBatchRatio=1.0 to keep batching only for exact gain ties. >1 costs ~one pass via the panel kernel (default 8)
#'   \item \code{leafBatchRatio}: Only batch leaves whose gain >= ratio * pass-best (0 = off; 1.0 reproduces exact best-first; ~0.2 measured to IMPROVE holdout AUC past both exact best-first and the CPU engine at ~20\% extra fit time — docs/perf_histogram.md) (default 0.0)
#'   \item \code{leafPredictionCol}: Output column for leaf indices ('' = off) (default '')
#'   \item \code{learningRate}: Shrinkage rate (default 0.1)
#'   \item \code{maxBin}: Max number of feature bins (default 255)
#'   \item \code{maxBinByFeature}: Per-feature max-bin override (empty = maxBin everywhere) (default [])
#'   \item \code{maxCatThreshold}: Max categories in a categorical split's left set (default 32)
#'   \item \code{maxCatToOnehot}: Categorical features with at most this many seen categories use the one-vs-rest split search instead of the sorted-set algorithm (native LightGBM max_cat_to_onehot) (default 4)
#'   \item \code{maxConflictRate}: EFB conflict budget (native max_conflict_rate): fraction of sampled rows where two bundled features may be simultaneously non-default. 0.0 = only perfectly exclusive features bundle (lossless); small values (e.g. 0.05) bundle harder at a bounded accuracy cost on conflict rows (default 0.0)
#'   \item \code{maxDeltaStep}: Max leaf output magnitude (0=off) (default 0.0)
#'   \item \code{maxDepth}: Max tree depth (-1 = derive from numLeaves) (default -1)
#'   \item \code{maxPosition}: Accepted for parity (NDCG optimization position) (default 20)
#'   \item \code{metric}: Eval metric name ('' = objective default) (default '')
#'   \item \code{minDataInLeaf}: Minimum rows per leaf (default 20)
#'   \item \code{minDataPerGroup}: Minimal rows a category needs to enter the sorted-set split search (native LightGBM min_data_per_group; the one-vs-rest path is exempt) (default 100)
#'   \item \code{minGainToSplit}: Minimum gain to split (default 0.0)
#'   \item \code{minSumHessianInLeaf}: Minimum hessian sum per leaf (default 0.001)
#'   \item \code{modelString}: Warm-start booster string (default '')
#'   \item \code{negBaggingFraction}: Negative-class bagging fraction (binary; 1.0 = off) (default 1.0)
#'   \item \code{numBatches}: Split training into sequential batches (0=off) (default 0)
#'   \item \code{numExecutors}: Run the histogram-binning prepass as partitioned tasks on this many fault-tolerant executors (mmlspark_tpu.runtime): bounded retries, heartbeat-loss re-dispatch, and lineage recompute apply, and the binned matrix is bit-identical to the inline pass. 0 (default) bins inline; an ambient runtime.policy() also activates the scheduler (default 0)
#'   \item \code{numIterations}: Number of boosting iterations (default 100)
#'   \item \code{numLeaves}: Max leaves per tree (default 31)
#'   \item \code{numProcesses}: Run the fit itself across this many real worker processes under a supervised gang (mmlspark_tpu.runtime.procgroup): each process fits a contiguous row shard, histograms allreduce over sockets, and a process killed mid-fit triggers gang recovery that resumes from the fit journal with zero re-execution of committed iterations. The distributed analog of the reference's per-executor native fit. 0/1 (default) fits in-process. Process mode restricts options (no bagging/GOSS/dart, no validation sets); see lightgbm.procfit.validate_process_options (default 0)
#'   \item \code{numTasks}: Override number of mesh shards (0 = all devices) (default 0)
#'   \item \code{objective}: Ranking objective (default 'lambdarank')
#'   \item \code{otherRate}: GOSS: sampled fraction of remaining rows (default 0.1)
#'   \item \code{parallelism}: data_parallel, voting_parallel, or serial (default 'data_parallel')
#'   \item \code{posBaggingFraction}: Positive-class bagging fraction (binary; 1.0 = off) (default 1.0)
#'   \item \code{predictionCol}: The name of the prediction column (default 'prediction')
#'   \item \code{seed}: Master seed (default 0)
#'   \item \code{sigma}: LambdaRank sigmoid steepness (default 1.0)
#'   \item \code{slotNames}: Feature slot names (overrides the generated f0..fN; also the namespace categoricalSlotNames resolves against) (default [])
#'   \item \code{topK}: Top features for voting parallel (default 20)
#'   \item \code{topRate}: GOSS: kept fraction of large-gradient rows (default 0.2)
#'   \item \code{useQuantizedGrad}: LightGBM's gradient-quantization training (use_quantized_grad): stochastically round g/h to an 8-bit per-tree grid so the histogram pass runs on the integer MXU (~15\% faster fits at the bench shape, docs/perf_histogram.md). Per-bin sums stay unbiased and counts exact; off (default) keeps bit-exact bf16 stats. Requires the precomputed-U path (single-device, maxBin <= 255, U within the HBM budget) and < 2^24 rows (f32 count exactness) — otherwise training logs a warning and proceeds with exact stats. Depthwise fits with depth >= 7 exceed the 128-slot U panel budget on deep levels (> 42 frontier nodes) and fall back per-level to exact histograms, logged once per fit (default False)
#'   \item \code{useSingleDatasetMode}: Accepted for API parity (dataset is always host-resident) (default True)
#'   \item \code{validationIndicatorCol}: Boolean column marking rows used for validation / early stopping
#'   \item \code{verbosity}: Verbosity (default -1)
#'   \item \code{weightCol}: The name of the instance-weight column
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_light_gbm_ranker <- function(...) {
  mt_stage("mmlspark_tpu.lightgbm.ranker", "LightGBMRanker", ...)
}

#' LightGBMRankerModel (Model)
#'
#' Shared model surface: booster access, native-model serde, leaf output.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{boosterData}: Fitted booster state
#'   \item \code{featuresCol}: The name of the features column (default 'features')
#'   \item \code{featuresShapCol}: Output column for SHAP values ('' = off) (default '')
#'   \item \code{leafPredictionCol}: Output column for leaf indices ('' = off) (default '')
#'   \item \code{predictionCol}: The name of the prediction column (default 'prediction')
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_light_gbm_ranker_model <- function(...) {
  mt_stage("mmlspark_tpu.lightgbm.ranker", "LightGBMRankerModel", ...)
}

#' LightGBMRegressionModel (Model)
#'
#' Shared model surface: booster access, native-model serde, leaf output.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{boosterData}: Fitted booster state
#'   \item \code{featuresCol}: The name of the features column (default 'features')
#'   \item \code{featuresShapCol}: Output column for SHAP values ('' = off) (default '')
#'   \item \code{leafPredictionCol}: Output column for leaf indices ('' = off) (default '')
#'   \item \code{objective}: Objective the booster was trained with (default 'regression')
#'   \item \code{predictionCol}: The name of the prediction column (default 'prediction')
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_light_gbm_regression_model <- function(...) {
  mt_stage("mmlspark_tpu.lightgbm.regressor", "LightGBMRegressionModel", ...)
}

#' LightGBMRegressor (Estimator)
#'
#' Shared fit flow (LightGBMBase.scala:26-213).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{alpha}: Quantile/huber alpha (default 0.9)
#'   \item \code{baggingFraction}: Row subsample fraction (default 1.0)
#'   \item \code{baggingFreq}: Resample bagging mask every k iterations (0=off) (default 0)
#'   \item \code{baggingSeed}: Bagging seed (default 3)
#'   \item \code{binSampleCount}: Rows sampled when computing histogram bin edges (bin_construct_sample_cnt) (default 200000)
#'   \item \code{boostFromAverage}: Start boosting from the label average init score (false = from 0) (default True)
#'   \item \code{boostingType}: gbdt, rf, dart, or goss (default 'gbdt')
#'   \item \code{catL2}: Extra L2 applied to categorical split gains (default 10.0)
#'   \item \code{catSmooth}: Smoothing for the categorical g/h bin ordering (default 10.0)
#'   \item \code{categoricalSlotIndexes}: Feature indexes treated as categorical (value-identity bins + LightGBM sorted-set split search) (default [])
#'   \item \code{categoricalSlotNames}: Feature names treated as categorical (resolved against the assembled feature names, e.g. 'f3') (default [])
#'   \item \code{dropRate}: DART: per-tree dropout probability (default 0.1)
#'   \item \code{earlyStoppingRound}: Stop after k rounds without improvement (0=off) (default 0)
#'   \item \code{featureBundling}: Exclusive Feature Bundling (native enable_bundle): greedily pack (near-)mutually-exclusive features into shared bin columns at binning time. Shrinks K = sum_f bins_f — the HBM re-stream that bounds every histogram pass — and the column count, so sparse/one-hot matrices fit the precomputed-U budget at row counts that previously overflowed it. Splits, model text, SHAP, and prediction stay in original feature space (emitted models are indistinguishable from unbundled fits; with zero bundling conflicts the tree structure is identical). Off by default — the native engine defaults on, but bundled histogram g/h for a member's default bin are recovered by subtraction, so float leaf values can differ in the last ulp from an unbundled fit (default False)
#'   \item \code{featureFraction}: Feature subsample fraction per tree (default 1.0)
#'   \item \code{featuresCol}: The name of the features column (default 'features')
#'   \item \code{featuresShapCol}: Output column for SHAP values ('' = off) (default '')
#'   \item \code{growthPolicy}: leafwise (LightGBM best-first, numLeaves-bounded) or depthwise (balanced levels — fewer, larger MXU passes) (default 'leafwise')
#'   \item \code{improvementTolerance}: Minimal delta counted as improvement (default 0.0)
#'   \item \code{initScoreCol}: The name of the initial-score (margin) column for warm start
#'   \item \code{isProvideTrainingMetric}: Record the train-set metric each iteration (evals['training']) (default False)
#'   \item \code{labelCol}: The name of the label column (default 'label')
#'   \item \code{lambdaL1}: L1 regularization (default 0.0)
#'   \item \code{lambdaL2}: L2 regularization (default 0.0)
#'   \item \code{leafBatch}: Frontier leaves split per histogram pass under leafwise growth. NOTE: the default (8) is a batched APPROXIMATION of LightGBM's sequential best-first growth — up to 8 frontier leaves commit together, so default fits are not best-first-exact and differ slightly from the native engine's trees (bench AUC delta ~0.001, docs/perf_histogram.md). Set leafBatch=1 for the exact sequential algorithm (~4x slower), or leafBatchRatio=1.0 to keep batching only for exact gain ties. >1 costs ~one pass via the panel kernel (default 8)
#'   \item \code{leafBatchRatio}: Only batch leaves whose gain >= ratio * pass-best (0 = off; 1.0 reproduces exact best-first; ~0.2 measured to IMPROVE holdout AUC past both exact best-first and the CPU engine at ~20\% extra fit time — docs/perf_histogram.md) (default 0.0)
#'   \item \code{leafPredictionCol}: Output column for leaf indices ('' = off) (default '')
#'   \item \code{learningRate}: Shrinkage rate (default 0.1)
#'   \item \code{maxBin}: Max number of feature bins (default 255)
#'   \item \code{maxBinByFeature}: Per-feature max-bin override (empty = maxBin everywhere) (default [])
#'   \item \code{maxCatThreshold}: Max categories in a categorical split's left set (default 32)
#'   \item \code{maxCatToOnehot}: Categorical features with at most this many seen categories use the one-vs-rest split search instead of the sorted-set algorithm (native LightGBM max_cat_to_onehot) (default 4)
#'   \item \code{maxConflictRate}: EFB conflict budget (native max_conflict_rate): fraction of sampled rows where two bundled features may be simultaneously non-default. 0.0 = only perfectly exclusive features bundle (lossless); small values (e.g. 0.05) bundle harder at a bounded accuracy cost on conflict rows (default 0.0)
#'   \item \code{maxDeltaStep}: Max leaf output magnitude (0=off) (default 0.0)
#'   \item \code{maxDepth}: Max tree depth (-1 = derive from numLeaves) (default -1)
#'   \item \code{metric}: Eval metric name ('' = objective default) (default '')
#'   \item \code{minDataInLeaf}: Minimum rows per leaf (default 20)
#'   \item \code{minDataPerGroup}: Minimal rows a category needs to enter the sorted-set split search (native LightGBM min_data_per_group; the one-vs-rest path is exempt) (default 100)
#'   \item \code{minGainToSplit}: Minimum gain to split (default 0.0)
#'   \item \code{minSumHessianInLeaf}: Minimum hessian sum per leaf (default 0.001)
#'   \item \code{modelString}: Warm-start booster string (default '')
#'   \item \code{negBaggingFraction}: Negative-class bagging fraction (binary; 1.0 = off) (default 1.0)
#'   \item \code{numBatches}: Split training into sequential batches (0=off) (default 0)
#'   \item \code{numExecutors}: Run the histogram-binning prepass as partitioned tasks on this many fault-tolerant executors (mmlspark_tpu.runtime): bounded retries, heartbeat-loss re-dispatch, and lineage recompute apply, and the binned matrix is bit-identical to the inline pass. 0 (default) bins inline; an ambient runtime.policy() also activates the scheduler (default 0)
#'   \item \code{numIterations}: Number of boosting iterations (default 100)
#'   \item \code{numLeaves}: Max leaves per tree (default 31)
#'   \item \code{numProcesses}: Run the fit itself across this many real worker processes under a supervised gang (mmlspark_tpu.runtime.procgroup): each process fits a contiguous row shard, histograms allreduce over sockets, and a process killed mid-fit triggers gang recovery that resumes from the fit journal with zero re-execution of committed iterations. The distributed analog of the reference's per-executor native fit. 0/1 (default) fits in-process. Process mode restricts options (no bagging/GOSS/dart, no validation sets); see lightgbm.procfit.validate_process_options (default 0)
#'   \item \code{numTasks}: Override number of mesh shards (0 = all devices) (default 0)
#'   \item \code{objective}: regression objective (default 'regression')
#'   \item \code{otherRate}: GOSS: sampled fraction of remaining rows (default 0.1)
#'   \item \code{parallelism}: data_parallel, voting_parallel, or serial (default 'data_parallel')
#'   \item \code{posBaggingFraction}: Positive-class bagging fraction (binary; 1.0 = off) (default 1.0)
#'   \item \code{predictionCol}: The name of the prediction column (default 'prediction')
#'   \item \code{seed}: Master seed (default 0)
#'   \item \code{slotNames}: Feature slot names (overrides the generated f0..fN; also the namespace categoricalSlotNames resolves against) (default [])
#'   \item \code{topK}: Top features for voting parallel (default 20)
#'   \item \code{topRate}: GOSS: kept fraction of large-gradient rows (default 0.2)
#'   \item \code{tweedieVariancePower}: Tweedie variance power in (1, 2) (default 1.5)
#'   \item \code{useQuantizedGrad}: LightGBM's gradient-quantization training (use_quantized_grad): stochastically round g/h to an 8-bit per-tree grid so the histogram pass runs on the integer MXU (~15\% faster fits at the bench shape, docs/perf_histogram.md). Per-bin sums stay unbiased and counts exact; off (default) keeps bit-exact bf16 stats. Requires the precomputed-U path (single-device, maxBin <= 255, U within the HBM budget) and < 2^24 rows (f32 count exactness) — otherwise training logs a warning and proceeds with exact stats. Depthwise fits with depth >= 7 exceed the 128-slot U panel budget on deep levels (> 42 frontier nodes) and fall back per-level to exact histograms, logged once per fit (default False)
#'   \item \code{useSingleDatasetMode}: Accepted for API parity (dataset is always host-resident) (default True)
#'   \item \code{validationIndicatorCol}: Boolean column marking rows used for validation / early stopping
#'   \item \code{verbosity}: Verbosity (default -1)
#'   \item \code{weightCol}: The name of the instance-weight column
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_light_gbm_regressor <- function(...) {
  mt_stage("mmlspark_tpu.lightgbm.regressor", "LightGBMRegressor", ...)
}

#' ImageLIME (Transformer)
#'
#' Superpixel-mask perturbation explanation for images (``lime/LIME.scala:251+``): output weight i = importance of superpixel i.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{cellSize}: Superpixel grid size (default 16)
#'   \item \code{inputCol}: The name of the input column
#'   \item \code{model}: Model to locally approximate (default None)
#'   \item \code{modifier}: SLIC compactness (default 130.0)
#'   \item \code{nSamples}: Number of perturbed samples per row (default 1000)
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{predictionCol}: The name of the prediction column (default 'prediction')
#'   \item \code{regularization}: Lasso lambda (0 = least squares) (default 0.0)
#'   \item \code{samplingFraction}: Fraction of superpixels kept on (default 0.3)
#'   \item \code{seed}: Perturbation RNG seed (default 0)
#'   \item \code{superpixelCol}: Superpixel decomposition column (default 'superpixels')
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_image_lime <- function(...) {
  mt_stage("mmlspark_tpu.lime.lime", "ImageLIME", ...)
}

#' TabularLIME (Estimator)
#'
#' fit() records per-column mean/std used for gaussian perturbations (``TabularLIME.fit`` runs a StandardScaler, ``lime/LIME.scala:173-185``).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{inputCol}: The name of the input column
#'   \item \code{model}: Model to locally approximate (default None)
#'   \item \code{nSamples}: Number of perturbed samples per row (default 1000)
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{predictionCol}: The name of the prediction column (default 'prediction')
#'   \item \code{regularization}: Lasso lambda (0 = least squares) (default 0.0)
#'   \item \code{samplingFraction}: Fraction of superpixels kept on (default 0.3)
#'   \item \code{seed}: Perturbation RNG seed (default 0)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_tabular_lime <- function(...) {
  mt_stage("mmlspark_tpu.lime.lime", "TabularLIME", ...)
}

#' TabularLIMEModel (Model)
#'
#' Per row: ``nSamples`` gaussian draws ``N(columnMeans, columnSTDs)`` (``TabularLIMEModel.perturbedDenseVectors``, ``lime/LIME.scala:215-221``), inner-model predictions, then lasso weights as the explanation.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{columnMeans}: Feature means for perturbation (default None)
#'   \item \code{columnSTDs}: Feature stds for perturbation (default None)
#'   \item \code{inputCol}: The name of the input column
#'   \item \code{model}: Model to locally approximate (default None)
#'   \item \code{nSamples}: Number of perturbed samples per row (default 1000)
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{predictionCol}: The name of the prediction column (default 'prediction')
#'   \item \code{regularization}: Lasso lambda (0 = least squares) (default 0.0)
#'   \item \code{samplingFraction}: Fraction of superpixels kept on (default 0.3)
#'   \item \code{seed}: Perturbation RNG seed (default 0)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_tabular_lime_model <- function(...) {
  mt_stage("mmlspark_tpu.lime.lime", "TabularLIMEModel", ...)
}

#' SuperpixelTransformer (Transformer)
#'
#' Image column -> superpixel decomposition column (``lime/Superpixel.scala`` SuperpixelTransformer).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{cellSize}: Approximate superpixel grid size in pixels (default 16)
#'   \item \code{inputCol}: The name of the input column
#'   \item \code{modifier}: SLIC compactness (default 130.0)
#'   \item \code{outputCol}: The name of the output column
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_superpixel_transformer <- function(...) {
  mt_stage("mmlspark_tpu.lime.superpixel", "SuperpixelTransformer", ...)
}

#' ConditionalKNN (Estimator)
#'
#' KNN whose matches are restricted per query to a set of labels (``nn/BallTree.scala:203``; fit injection ``OptimizedCKNNFitting.scala:74``).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{conditionerCol}: Query column holding the set of admissible labels (default 'conditioner')
#'   \item \code{featuresCol}: The name of the features column (default 'features')
#'   \item \code{k}: Number of matches to return (default 5)
#'   \item \code{labelCol}: Column of index labels for conditional queries (default 'labels')
#'   \item \code{leafSize}: Max leaf size of the ball tree (default 50)
#'   \item \code{method}: Query engine: 'brute' (on-chip matmul top-k) or 'balltree' (host) (default 'brute')
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{valuesCol}: Column of values returned for each match (default 'values')
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_conditional_knn <- function(...) {
  mt_stage("mmlspark_tpu.nn.knn", "ConditionalKNN", ...)
}

#' ConditionalKNNModel (Model)
#'
#' Shared params (``nn/KNN.scala:21-44``).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{conditionerCol}: Query column holding the set of admissible labels (default 'conditioner')
#'   \item \code{featuresCol}: The name of the features column (default 'features')
#'   \item \code{indexKeys}: Index key matrix (n × d) (default None)
#'   \item \code{indexLabels}: Per-row labels filtered by the conditioner (default None)
#'   \item \code{indexValues}: Per-row values returned on match (default None)
#'   \item \code{k}: Number of matches to return (default 5)
#'   \item \code{labelCol}: Column of index labels for conditional queries (default 'labels')
#'   \item \code{leafSize}: Max leaf size of the ball tree (default 50)
#'   \item \code{method}: Query engine: 'brute' (on-chip matmul top-k) or 'balltree' (host) (default 'brute')
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{valuesCol}: Column of values returned for each match (default 'values')
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_conditional_knn_model <- function(...) {
  mt_stage("mmlspark_tpu.nn.knn", "ConditionalKNNModel", ...)
}

#' KNN (Estimator)
#'
#' Fits a MIPS index over (featuresCol, valuesCol) rows.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{featuresCol}: The name of the features column (default 'features')
#'   \item \code{k}: Number of matches to return (default 5)
#'   \item \code{leafSize}: Max leaf size of the ball tree (default 50)
#'   \item \code{method}: Query engine: 'brute' (on-chip matmul top-k) or 'balltree' (host) (default 'brute')
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{valuesCol}: Column of values returned for each match (default 'values')
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_knn <- function(...) {
  mt_stage("mmlspark_tpu.nn.knn", "KNN", ...)
}

#' KNNModel (Model)
#'
#' Shared params (``nn/KNN.scala:21-44``).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{featuresCol}: The name of the features column (default 'features')
#'   \item \code{indexKeys}: Index key matrix (n × d) (default None)
#'   \item \code{indexValues}: Per-row values returned on match (default None)
#'   \item \code{k}: Number of matches to return (default 5)
#'   \item \code{leafSize}: Max leaf size of the ball tree (default 50)
#'   \item \code{method}: Query engine: 'brute' (on-chip matmul top-k) or 'balltree' (host) (default 'brute')
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{valuesCol}: Column of values returned for each match (default 'values')
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_knn_model <- function(...) {
  mt_stage("mmlspark_tpu.nn.knn", "KNNModel", ...)
}

#' RankingAdapter (Estimator)
#'
#' Wraps a recommender Estimator so its output can feed :class:`RankingEvaluator` (``RankingAdapter.scala:67-97``).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{k}: Recommendations per user (default 10)
#'   \item \code{labelCol}: Output column of per-user actual items (default 'label')
#'   \item \code{mode}: allUsers (recommendForAllUsers) (default 'allUsers')
#'   \item \code{recommender}: The wrapped recommender estimator
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_ranking_adapter <- function(...) {
  mt_stage("mmlspark_tpu.recommendation.ranking", "RankingAdapter", ...)
}

#' RankingAdapterModel (Model)
#'
#' transform(): per-user top-k ground truth (by rating desc) joined with the recommender's top-k predictions (``RankingAdapter.scala:116-141``).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{k}: Recommendations per user (default 10)
#'   \item \code{labelCol}: Per-user actual item lists (default 'label')
#'   \item \code{mode}: allUsers (default 'allUsers')
#'   \item \code{recommenderModel}: Fitted recommender (default None)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_ranking_adapter_model <- function(...) {
  mt_stage("mmlspark_tpu.recommendation.ranking", "RankingAdapterModel", ...)
}

#' RankingTrainValidationSplit (Estimator)
#'
#' User-stratified train/validation split + grid evaluation (``RankingTrainValidationSplit.scala:24-328``). Rows of users/items with fewer than ``minRatingsU``/``minRatingsI`` events are dropped, each user's events are split by ``trainRatio``, and each param map is fitted on train / scored on validation with :class:`RankingEvaluator`.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{estimator}: Recommender estimator (fit via RankingAdapter)
#'   \item \code{estimatorParamMaps}: Param maps to sweep (list of dicts) (default None)
#'   \item \code{evaluator}: RankingEvaluator (default None)
#'   \item \code{itemCol}: Item column (default 'item')
#'   \item \code{minRatingsI}: Min events per item (default 1)
#'   \item \code{minRatingsU}: Min events per user (default 1)
#'   \item \code{ratingCol}: Rating column (default 'rating')
#'   \item \code{seed}: Split RNG seed (default 42)
#'   \item \code{trainRatio}: Fraction of each user's events in train (default 0.75)
#'   \item \code{userCol}: User column (default 'user')
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_ranking_train_validation_split <- function(...) {
  mt_stage("mmlspark_tpu.recommendation.ranking", "RankingTrainValidationSplit", ...)
}

#' RankingTrainValidationSplitModel (Model)
#'
#' A fitted Transformer produced by an Estimator.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{bestModel}: Best RankingAdapterModel (default None)
#'   \item \code{validationMetrics}: Metric per param map (default None)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_ranking_train_validation_split_model <- function(...) {
  mt_stage("mmlspark_tpu.recommendation.ranking", "RankingTrainValidationSplitModel", ...)
}

#' RecommendationIndexer (Estimator)
#'
#' User/item value → dense index, with inverse transform (``RecommendationIndexer.scala:17-101``); composed from two :class:`ValueIndexer` fits.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{itemInputCol}: Raw item column
#'   \item \code{itemOutputCol}: Indexed item column
#'   \item \code{ratingCol}: Rating column (passed through) (default 'rating')
#'   \item \code{userInputCol}: Raw user column
#'   \item \code{userOutputCol}: Indexed user column
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_recommendation_indexer <- function(...) {
  mt_stage("mmlspark_tpu.recommendation.ranking", "RecommendationIndexer", ...)
}

#' RecommendationIndexerModel (Model)
#'
#' A fitted Transformer produced by an Estimator.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{itemIndexModel}: Fitted item ValueIndexerModel (default None)
#'   \item \code{itemInputCol}: Raw item column
#'   \item \code{itemOutputCol}: Indexed item column
#'   \item \code{userIndexModel}: Fitted user ValueIndexerModel (default None)
#'   \item \code{userInputCol}: Raw user column
#'   \item \code{userOutputCol}: Indexed user column
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_recommendation_indexer_model <- function(...) {
  mt_stage("mmlspark_tpu.recommendation.ranking", "RecommendationIndexerModel", ...)
}

#' SAR (Estimator)
#'
#' Fits user-affinity + item-similarity matrices from an event table.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{itemCol}: Item id column (integer ids) (default 'item')
#'   \item \code{ratingCol}: Rating column (optional) (default 'rating')
#'   \item \code{similarityFunction}: jaccard | lift | cooccurrence (``SAR.scala:150-207``) (default 'jaccard')
#'   \item \code{startTime}: Reference time (ISO string); default = max event time (default None)
#'   \item \code{supportThreshold}: Min co-occurrence count for a nonzero similarity (default 4)
#'   \item \code{timeCol}: Event-time column (optional) (default 'timestamp')
#'   \item \code{timeDecayCoeff}: Half-life of the affinity decay, in days (default 30)
#'   \item \code{userCol}: User id column (integer ids; see RecommendationIndexer) (default 'user')
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_sar <- function(...) {
  mt_stage("mmlspark_tpu.recommendation.sar", "SAR", ...)
}

#' SARModel (Model)
#'
#' Holds the dense affinity/similarity factors (``SARModel.userDataFrame``/``itemDataFrame`` analogues).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{itemCol}: Item id column (integer ids) (default 'item')
#'   \item \code{itemSimilarity}: Item×item similarity matrix (default None)
#'   \item \code{ratingCol}: Rating column (optional) (default 'rating')
#'   \item \code{similarityFunction}: jaccard | lift | cooccurrence (``SAR.scala:150-207``) (default 'jaccard')
#'   \item \code{startTime}: Reference time (ISO string); default = max event time (default None)
#'   \item \code{supportThreshold}: Min co-occurrence count for a nonzero similarity (default 4)
#'   \item \code{timeCol}: Event-time column (optional) (default 'timestamp')
#'   \item \code{timeDecayCoeff}: Half-life of the affinity decay, in days (default 30)
#'   \item \code{userAffinity}: User×item affinity matrix (default None)
#'   \item \code{userCol}: User id column (integer ids; see RecommendationIndexer) (default 'user')
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_sar_model <- function(...) {
  mt_stage("mmlspark_tpu.recommendation.sar", "SARModel", ...)
}

#' Cacher (Transformer)
#'
#' Materialization point (``stages/Cacher.scala``). Tables are already host-materialized, so this is an explicit no-op kept for pipeline parity.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{disable}: If true, do not cache (default False)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_cacher <- function(...) {
  mt_stage("mmlspark_tpu.stages.basic", "Cacher", ...)
}

#' ClassBalancer (Estimator)
#'
#' Adds a weight column inversely proportional to label frequency (``stages/ClassBalancer.scala:27``).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{broadcastJoin}: Whether to broadcast the weight table (no-op hint here) (default True)
#'   \item \code{inputCol}: The name of the input column
#'   \item \code{outputCol}: Weight column name (default 'weight')
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_class_balancer <- function(...) {
  mt_stage("mmlspark_tpu.stages.basic", "ClassBalancer", ...)
}

#' ClassBalancerModel (Model)
#'
#' A fitted Transformer produced by an Estimator.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{inputCol}: The name of the input column
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{weights}: label value -> weight (default {})
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_class_balancer_model <- function(...) {
  mt_stage("mmlspark_tpu.stages.basic", "ClassBalancerModel", ...)
}

#' DropColumns (Transformer)
#'
#' Drop the listed columns (``stages/DropColumns.scala``).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{cols}: Columns to remove
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_drop_columns <- function(...) {
  mt_stage("mmlspark_tpu.stages.basic", "DropColumns", ...)
}

#' EnsembleByKey (Transformer)
#'
#' Aggregate scalar/vector columns grouped by key columns (``stages/EnsembleByKey.scala:22``).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{colNames}: Output names (default: '<strategy>(<col>)')
#'   \item \code{collapseGroup}: If true, one row per key; else broadcast the aggregate back to all rows (default True)
#'   \item \code{cols}: Columns to aggregate
#'   \item \code{keys}: Grouping key columns
#'   \item \code{strategy}: Aggregation strategy (default 'mean')
#'   \item \code{vectorDims}: Kept for parity; dims inferred from data (default None)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_ensemble_by_key <- function(...) {
  mt_stage("mmlspark_tpu.stages.basic", "EnsembleByKey", ...)
}

#' Explode (Transformer)
#'
#' One output row per element of a ragged/list column (``stages/Explode.scala``); other columns are repeated.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{inputCol}: The name of the input column
#'   \item \code{outputCol}: The name of the output column
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_explode <- function(...) {
  mt_stage("mmlspark_tpu.stages.basic", "Explode", ...)
}

#' Lambda (Transformer)
#'
#' Arbitrary ``Table -> Table`` function as a pipeline stage (``stages/Lambda.scala:21``). The function is a complex param (pickle-serialized), like the reference's UDF ComplexParam.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{transformFunc}: Table -> Table function
#'   \item \code{transformSchemaFunc}: schema -> schema function (optional) (default None)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_lambda <- function(...) {
  mt_stage("mmlspark_tpu.stages.basic", "Lambda", ...)
}

#' MultiColumnAdapter (Estimator)
#'
#' Map a single-column stage over many column pairs (``stages/MultiColumnAdapter.scala:18``).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{baseStage}: Stage to replicate per column
#'   \item \code{inputCols}: The names of the input columns
#'   \item \code{outputCols}: The names of the output columns
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_multi_column_adapter <- function(...) {
  mt_stage("mmlspark_tpu.stages.basic", "MultiColumnAdapter", ...)
}

#' RenameColumn (Transformer)
#'
#' Rename ``inputCol`` to ``outputCol`` (``stages/RenameColumn.scala``).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{inputCol}: Column to rename
#'   \item \code{outputCol}: New column name
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_rename_column <- function(...) {
  mt_stage("mmlspark_tpu.stages.basic", "RenameColumn", ...)
}

#' Repartition (Transformer)
#'
#' Change the logical partition count (``stages/Repartition.scala``).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{disable}: If true, pass through unchanged (default False)
#'   \item \code{n}: Number of partitions
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_repartition <- function(...) {
  mt_stage("mmlspark_tpu.stages.basic", "Repartition", ...)
}

#' SelectColumns (Transformer)
#'
#' Keep only the listed columns (``stages/SelectColumns.scala``).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{cols}: Columns to keep
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_select_columns <- function(...) {
  mt_stage("mmlspark_tpu.stages.basic", "SelectColumns", ...)
}

#' StratifiedRepartition (Transformer)
#'
#' Rebalance rows so every partition sees every label value (``stages/StratifiedRepartition.scala:29``).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{labelCol}: The name of the label column (default 'label')
#'   \item \code{mode}: equal, original, or mixed distribution of labels (default 'mixed')
#'   \item \code{seed}: Random seed (default 0)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_stratified_repartition <- function(...) {
  mt_stage("mmlspark_tpu.stages.basic", "StratifiedRepartition", ...)
}

#' SummarizeData (Transformer)
#'
#' Per-column summary statistics table (``stages/SummarizeData.scala:100``): counts, missing, basic moments, and error-bounded quantiles.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{basic}: Include basic stats (default True)
#'   \item \code{counts}: Include count stats (default True)
#'   \item \code{errorThreshold}: Quantile error (0 = exact) (default 0.0)
#'   \item \code{percentiles}: Include percentiles (default True)
#'   \item \code{sample}: Include sample stats (default True)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_summarize_data <- function(...) {
  mt_stage("mmlspark_tpu.stages.basic", "SummarizeData", ...)
}

#' TextPreprocessor (Transformer)
#'
#' Trie-based substring mapping (``stages/TextPreprocessor.scala:96``): longest-match replacement of every ``map`` key found in the text.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{inputCol}: The name of the input column
#'   \item \code{map}: substring -> replacement (default {})
#'   \item \code{normFunc}: Normalization applied before matching: identity|lowerCase|upperCase (default 'identity')
#'   \item \code{outputCol}: The name of the output column
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_text_preprocessor <- function(...) {
  mt_stage("mmlspark_tpu.stages.basic", "TextPreprocessor", ...)
}

#' Timer (Estimator)
#'
#' Wraps a stage; logs fit/transform wall time (``stages/Timer.scala:57``).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{disableMaterialization}: Kept for reference parity; Tables are always materialized (default True)
#'   \item \code{logToScala}: Log with the framework logger (default True)
#'   \item \code{stage}: The wrapped stage
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_timer <- function(...) {
  mt_stage("mmlspark_tpu.stages.basic", "Timer", ...)
}

#' TimerModel (Model)
#'
#' A fitted Transformer produced by an Estimator.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{logToScala}: Log with the framework logger (default True)
#'   \item \code{stage}: The wrapped fitted stage
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_timer_model <- function(...) {
  mt_stage("mmlspark_tpu.stages.basic", "TimerModel", ...)
}

#' UDFTransformer (Transformer)
#'
#' Applies a column function to one or many input columns (``stages/UDFTransformer.scala``). ``udf`` receives whole column arrays (vectorized), not scalar rows.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{inputCol}: The name of the input column
#'   \item \code{inputCols}: The names of the input columns
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{udf}: Column-level function
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_udf_transformer <- function(...) {
  mt_stage("mmlspark_tpu.stages.basic", "UDFTransformer", ...)
}

#' UnicodeNormalize (Transformer)
#'
#' Unicode NFKD/NFC normalization + optional lower-casing (``stages/UnicodeNormalize.scala``).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{form}: Normalization form (default 'NFKD')
#'   \item \code{inputCol}: The name of the input column
#'   \item \code{lower}: Lower-case the text (default True)
#'   \item \code{outputCol}: The name of the output column
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_unicode_normalize <- function(...) {
  mt_stage("mmlspark_tpu.stages.basic", "UnicodeNormalize", ...)
}

#' DynamicMiniBatchTransformer (Transformer)
#'
#' Batch whatever is available, up to ``maxBatchSize`` (``stages/MiniBatchTransformer.scala:43``). Without a streaming queue the whole partition is 'available': each logical partition becomes one batch, capped at ``maxBatchSize`` rows.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{maxBatchSize}: Maximum rows per batch (default 2147483647)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_dynamic_mini_batch_transformer <- function(...) {
  mt_stage("mmlspark_tpu.stages.batching", "DynamicMiniBatchTransformer", ...)
}

#' FixedMiniBatchTransformer (Transformer)
#'
#' Group every ``batchSize`` consecutive rows into one batch row (``stages/MiniBatchTransformer.scala:139``).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{batchSize}: Rows per batch (default 10)
#'   \item \code{buffered}: Kept for parity (background buffering thread) (default False)
#'   \item \code{maxBufferSize}: Kept for parity; columnar batching needs no buffer (default -1)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_fixed_mini_batch_transformer <- function(...) {
  mt_stage("mmlspark_tpu.stages.batching", "FixedMiniBatchTransformer", ...)
}

#' FlattenBatch (Transformer)
#'
#' Invert mini-batching: explode every batched column back to one row per element (``stages/MiniBatchTransformer.scala:159``).
#' @param ... named params forwarded to the Python constructor
#' @export
mt_flatten_batch <- function(...) {
  mt_stage("mmlspark_tpu.stages.batching", "FlattenBatch", ...)
}

#' TimeIntervalMiniBatchTransformer (Transformer)
#'
#' Batch rows arriving within ``millisToWait`` of each other (``stages/MiniBatchTransformer.scala:95``). Materialized Tables have no arrival times; an explicit ``timestampCol`` (epoch millis) partitions rows into interval-gap batches, else one batch per partition.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{maxBatchSize}: Maximum rows per batch (default 2147483647)
#'   \item \code{millisToWait}: Interval in milliseconds (default 1000)
#'   \item \code{timestampCol}: Optional epoch-millis column defining arrival times (default None)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_time_interval_mini_batch_transformer <- function(...) {
  mt_stage("mmlspark_tpu.stages.batching", "TimeIntervalMiniBatchTransformer", ...)
}

#' TrainValidSweep (Estimator)
#'
#' Batched train/validation hyperparameter sweep with best-model commit. The batch-of-models analogue of ``TuneHyperparameters``: one split instead of k folds, shape-bucketed vmapped fits instead of a candidate-at-a-time thread pool.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{commitModel}: Commit the refit best model to the ModelStore (default True)
#'   \item \code{estimator}: Estimator to sweep
#'   \item \code{evaluationMetric}: Metric name (default 'accuracy')
#'   \item \code{labelCol}: The name of the label column (default 'label')
#'   \item \code{numProcesses}: Shard buckets across a worker gang when > 1 (default 0)
#'   \item \code{numRuns}: Sampled param maps (random spaces) (default 10)
#'   \item \code{paramMaps}: Explicit candidate param maps (overrides paramSpace) (default None)
#'   \item \code{paramSpace}: GridSpace / RandomSpace / {param: Dist} candidate source (default None)
#'   \item \code{seed}: RNG seed (sampling + split) (default 0)
#'   \item \code{trainRatio}: Fraction of rows in the training split (default 0.75)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_train_valid_sweep <- function(...) {
  mt_stage("mmlspark_tpu.sweep.estimator", "TrainValidSweep", ...)
}

#' TrainValidSweepModel (Model)
#'
#' A fitted Transformer produced by an Estimator.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{allMetrics}: Validation metric per candidate (default None)
#'   \item \code{bestMetric}: Winning validation metric (default nan)
#'   \item \code{bestModel}: Winning refit model (default None)
#'   \item \code{bestParams}: Winning param map (default None)
#'   \item \code{modelVersion}: ModelStore version of the committed best model (-1 = not committed) (default -1)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_train_valid_sweep_model <- function(...) {
  mt_stage("mmlspark_tpu.sweep.estimator", "TrainValidSweepModel", ...)
}

#' ComputeModelStatistics (Transformer)
#'
#' Scored table -> one-row metrics table.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{evaluationMetric}: classification | regression | auto (default 'auto')
#'   \item \code{labelCol}: The name of the label column (default 'label')
#'   \item \code{scoredProbabilitiesCol}: Probability column (binary AUC) (default 'probability')
#'   \item \code{scoresCol}: Prediction column (default 'prediction')
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_compute_model_statistics <- function(...) {
  mt_stage("mmlspark_tpu.train.statistics", "ComputeModelStatistics", ...)
}

#' ComputePerInstanceStatistics (Transformer)
#'
#' Appends per-row metrics (``ComputePerInstanceStatistics.scala:42``): regression -> L1/L2 loss; classification -> log-loss + correctness.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{evaluationMetric}: classification | regression | auto (default 'auto')
#'   \item \code{labelCol}: The name of the label column (default 'label')
#'   \item \code{scoredProbabilitiesCol}: Probability column (default 'probability')
#'   \item \code{scoresCol}: Prediction column (default 'prediction')
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_compute_per_instance_statistics <- function(...) {
  mt_stage("mmlspark_tpu.train.statistics", "ComputePerInstanceStatistics", ...)
}

#' TrainClassifier (Estimator)
#'
#' Featurize + reindex labels + fit a classifier.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{featuresCol}: Assembled features column (default 'TrainedFeatures')
#'   \item \code{labelCol}: The name of the label column (default 'label')
#'   \item \code{model}: The learner to fit
#'   \item \code{numFeatures}: Text hash dimensions during featurization (default 256)
#'   \item \code{reindexLabel}: Index string/sparse labels (default True)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_train_classifier <- function(...) {
  mt_stage("mmlspark_tpu.train.trainers", "TrainClassifier", ...)
}

#' TrainRegressor (Estimator)
#'
#' Base of all stages. Adds persistence (save/load).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{featuresCol}: Assembled features column (default 'TrainedFeatures')
#'   \item \code{labelCol}: The name of the label column (default 'label')
#'   \item \code{model}: The learner to fit
#'   \item \code{numFeatures}: Text hash dimensions during featurization (default 256)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_train_regressor <- function(...) {
  mt_stage("mmlspark_tpu.train.trainers", "TrainRegressor", ...)
}

#' TrainedClassifierModel (Model)
#'
#' A fitted Transformer produced by an Estimator.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{featuresCol}: The name of the features column (default 'features')
#'   \item \code{featurizerModel}: The fitted featurizer (None = passthrough) (default None)
#'   \item \code{fittedModel}: The fitted learner
#'   \item \code{labelCol}: The name of the label column (default 'label')
#'   \item \code{labelLevels}: Original label values (None = already indexed) (default None)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_trained_classifier_model <- function(...) {
  mt_stage("mmlspark_tpu.train.trainers", "TrainedClassifierModel", ...)
}

#' TrainedRegressorModel (Model)
#'
#' A fitted Transformer produced by an Estimator.
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{featuresCol}: The name of the features column (default 'features')
#'   \item \code{featurizerModel}: The fitted featurizer (None = passthrough) (default None)
#'   \item \code{fittedModel}: The fitted learner
#'   \item \code{labelCol}: The name of the label column (default 'label')
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_trained_regressor_model <- function(...) {
  mt_stage("mmlspark_tpu.train.trainers", "TrainedRegressorModel", ...)
}

#' VowpalWabbitBase (Estimator)
#'
#' Base of all stages. Adds persistence (save/load).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{batchSize}: Rows per SGD minibatch (default 64)
#'   \item \code{featuresCol}: The name of the features column (default 'features')
#'   \item \code{hashSeed}: Hash seed for the constant feature (default 0)
#'   \item \code{initialModel}: Warm-start weights
#'   \item \code{interactions}: Namespace interaction pairs (handled by VowpalWabbitInteractions) (default [])
#'   \item \code{l1}: L1 regularization (lazy, applied at pass end) (default 0.0)
#'   \item \code{l2}: L2 regularization (default 0.0)
#'   \item \code{labelCol}: The name of the label column (default 'label')
#'   \item \code{learningRate}: Base learning rate (default 0.5)
#'   \item \code{numBits}: log2 feature-space size (when features are dense) (default 18)
#'   \item \code{numPasses}: Training passes over the data (default 1)
#'   \item \code{passThroughArgs}: VW-style CLI arg string (parsed for known flags) (default '')
#'   \item \code{powerT}: Learning-rate decay exponent (default 0.5)
#'   \item \code{predictionCol}: The name of the prediction column (default 'prediction')
#'   \item \code{useBarrierExecutionMode}: Accepted for API parity (SPMD is always synchronous) (default True)
#'   \item \code{weightCol}: The name of the instance-weight column
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_vowpal_wabbit_base <- function(...) {
  mt_stage("mmlspark_tpu.vw.base", "VowpalWabbitBase", ...)
}

#' VowpalWabbitModelBase (Model)
#'
#' Shared model: weights + raw margin computation (``VowpalWabbitBaseModel.scala``).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{constantIndex}: Bias feature index (-1 = trained --noconstant) (default 0)
#'   \item \code{featuresCol}: The name of the features column (default 'features')
#'   \item \code{linkFunction}: Prediction link (--link): identity or logistic (default 'identity')
#'   \item \code{modelWeights}: Fitted weight vector
#'   \item \code{numBits}: log2 feature-space size for dense inputs (default 18)
#'   \item \code{predictionCol}: The name of the prediction column (default 'prediction')
#'   \item \code{sparseDim}: Feature-space size (default 0)
#'   \item \code{trainingStats}: Training diagnostics
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_vowpal_wabbit_model_base <- function(...) {
  mt_stage("mmlspark_tpu.vw.base", "VowpalWabbitModelBase", ...)
}

#' VowpalWabbitClassificationModel (Model)
#'
#' Shared model: weights + raw margin computation (``VowpalWabbitBaseModel.scala``).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{constantIndex}: Bias feature index (-1 = trained --noconstant) (default 0)
#'   \item \code{featuresCol}: The name of the features column (default 'features')
#'   \item \code{linkFunction}: Prediction link (--link): identity or logistic (default 'identity')
#'   \item \code{modelWeights}: Fitted weight vector
#'   \item \code{numBits}: log2 feature-space size for dense inputs (default 18)
#'   \item \code{predictionCol}: The name of the prediction column (default 'prediction')
#'   \item \code{probabilityCol}: Probability output column (default 'probability')
#'   \item \code{rawPredictionCol}: Raw margin output column (default 'rawPrediction')
#'   \item \code{sparseDim}: Feature-space size (default 0)
#'   \item \code{trainingStats}: Training diagnostics
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_vowpal_wabbit_classification_model <- function(...) {
  mt_stage("mmlspark_tpu.vw.classifier", "VowpalWabbitClassificationModel", ...)
}

#' VowpalWabbitClassifier (Estimator)
#'
#' Base of all stages. Adds persistence (save/load).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{batchSize}: Rows per SGD minibatch (default 64)
#'   \item \code{featuresCol}: The name of the features column (default 'features')
#'   \item \code{hashSeed}: Hash seed for the constant feature (default 0)
#'   \item \code{initialModel}: Warm-start weights
#'   \item \code{interactions}: Namespace interaction pairs (handled by VowpalWabbitInteractions) (default [])
#'   \item \code{l1}: L1 regularization (lazy, applied at pass end) (default 0.0)
#'   \item \code{l2}: L2 regularization (default 0.0)
#'   \item \code{labelCol}: The name of the label column (default 'label')
#'   \item \code{learningRate}: Base learning rate (default 0.5)
#'   \item \code{numBits}: log2 feature-space size (when features are dense) (default 18)
#'   \item \code{numPasses}: Training passes over the data (default 1)
#'   \item \code{passThroughArgs}: VW-style CLI arg string (parsed for known flags) (default '')
#'   \item \code{powerT}: Learning-rate decay exponent (default 0.5)
#'   \item \code{predictionCol}: The name of the prediction column (default 'prediction')
#'   \item \code{probabilityCol}: Probability output column (default 'probability')
#'   \item \code{rawPredictionCol}: Raw margin output column (default 'rawPrediction')
#'   \item \code{useBarrierExecutionMode}: Accepted for API parity (SPMD is always synchronous) (default True)
#'   \item \code{weightCol}: The name of the instance-weight column
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_vowpal_wabbit_classifier <- function(...) {
  mt_stage("mmlspark_tpu.vw.classifier", "VowpalWabbitClassifier", ...)
}

#' VowpalWabbitFeaturizer (Transformer)
#'
#' Base of all stages. Adds persistence (save/load).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{hashSeed}: Murmur hash seed (default 0)
#'   \item \code{inputCols}: The names of the input columns
#'   \item \code{numBits}: log2 of feature-space size (default 18)
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{prefixStringsWithColumnName}: Prefix hashed tokens with the column name (default True)
#'   \item \code{stringSplit}: Split string columns on whitespace into tokens (default False)
#'   \item \code{sumCollisions}: Sum values on hash collisions (vs keep first) (default True)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_vowpal_wabbit_featurizer <- function(...) {
  mt_stage("mmlspark_tpu.vw.featurizer", "VowpalWabbitFeaturizer", ...)
}

#' VowpalWabbitInteractions (Transformer)
#'
#' Base of all stages. Adds persistence (save/load).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{inputCols}: The names of the input columns
#'   \item \code{numBits}: log2 feature-space size (default 18)
#'   \item \code{outputCol}: The name of the output column
#'   \item \code{sumCollisions}: Sum values on hash collisions (default True)
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_vowpal_wabbit_interactions <- function(...) {
  mt_stage("mmlspark_tpu.vw.interactions", "VowpalWabbitInteractions", ...)
}

#' VowpalWabbitRegressionModel (Model)
#'
#' Shared model: weights + raw margin computation (``VowpalWabbitBaseModel.scala``).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{constantIndex}: Bias feature index (-1 = trained --noconstant) (default 0)
#'   \item \code{featuresCol}: The name of the features column (default 'features')
#'   \item \code{linkFunction}: Prediction link (--link): identity or logistic (default 'identity')
#'   \item \code{modelWeights}: Fitted weight vector
#'   \item \code{numBits}: log2 feature-space size for dense inputs (default 18)
#'   \item \code{predictionCol}: The name of the prediction column (default 'prediction')
#'   \item \code{sparseDim}: Feature-space size (default 0)
#'   \item \code{trainingStats}: Training diagnostics
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_vowpal_wabbit_regression_model <- function(...) {
  mt_stage("mmlspark_tpu.vw.regressor", "VowpalWabbitRegressionModel", ...)
}

#' VowpalWabbitRegressor (Estimator)
#'
#' Base of all stages. Adds persistence (save/load).
#'
#' @section Parameters:
#' \itemize{
#'   \item \code{batchSize}: Rows per SGD minibatch (default 64)
#'   \item \code{featuresCol}: The name of the features column (default 'features')
#'   \item \code{hashSeed}: Hash seed for the constant feature (default 0)
#'   \item \code{initialModel}: Warm-start weights
#'   \item \code{interactions}: Namespace interaction pairs (handled by VowpalWabbitInteractions) (default [])
#'   \item \code{l1}: L1 regularization (lazy, applied at pass end) (default 0.0)
#'   \item \code{l2}: L2 regularization (default 0.0)
#'   \item \code{labelCol}: The name of the label column (default 'label')
#'   \item \code{learningRate}: Base learning rate (default 0.5)
#'   \item \code{numBits}: log2 feature-space size (when features are dense) (default 18)
#'   \item \code{numPasses}: Training passes over the data (default 1)
#'   \item \code{passThroughArgs}: VW-style CLI arg string (parsed for known flags) (default '')
#'   \item \code{powerT}: Learning-rate decay exponent (default 0.5)
#'   \item \code{predictionCol}: The name of the prediction column (default 'prediction')
#'   \item \code{useBarrierExecutionMode}: Accepted for API parity (SPMD is always synchronous) (default True)
#'   \item \code{weightCol}: The name of the instance-weight column
#' }
#' @param ... named params forwarded to the Python constructor
#' @export
mt_vowpal_wabbit_regressor <- function(...) {
  mt_stage("mmlspark_tpu.vw.regressor", "VowpalWabbitRegressor", ...)
}
